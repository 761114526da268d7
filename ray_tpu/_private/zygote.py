"""Preforked worker template ("zygote") — the nodelet's fast spawn path
(reference: the worker-pool prestart/preload machinery in raylet's
WorkerPool + python worker preload; here an explicit fork server, which a
single-binary python runtime can do directly).

The zygote process pays the interpreter + ray_tpu import cost ONCE
(~0.6 s on this image), then serves fork requests over a unix socket:
each request carries the child's full environment + log path, and the
forked child IS a worker process a few milliseconds later. Only plain
CPU workers fork from here — TPU workers need their own interpreter
start (libtpu reads its chip bounds from the environment the process
starts with), and pip/uv runtime envs run under a different interpreter
entirely.

Fork safety: the zygote stays single-threaded (no event loops, no jax)
— it imports worker_main's module graph, binds the socket, and loops in
accept(). Children get SIGCHLD auto-reaped (SIG_IGN), a fresh session
(setsid), their own stdout/stderr log file, and a scrubbed environment.
"""

from __future__ import annotations

import os
import pickle
import signal
import socket
import struct
import sys


# The forked child's spawn connection, kept referenced (and thus open) for
# the child's whole life — its EOF is the nodelet-side liveness signal.
_keep_alive: list = []


def _recv_exact(conn: socket.socket, n: int) -> bytes:
    parts = []
    while n:
        b = conn.recv(n)
        if not b:
            raise ConnectionError("zygote request truncated")
        parts.append(b)
        n -= len(b)
    return b"".join(parts)


def spawn_via_zygote(sock_path: str, env: dict,
                     log_path: str) -> "tuple[int, socket.socket]":
    """Client side (nodelet): ask the zygote to fork one worker; returns
    (child pid, liveness socket). The CHILD keeps its end of this
    connection open for its whole life, so the caller gets an EOF-based
    liveness signal that — unlike a bare pid probe — cannot confuse a
    recycled pid with a live worker."""
    conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        conn.settimeout(10.0)
        conn.connect(sock_path)
        payload = pickle.dumps({"env": env, "log": log_path})
        conn.sendall(struct.pack(">I", len(payload)) + payload)
        (pid,) = struct.unpack(">q", _recv_exact(conn, 8))
        if pid < 0:
            raise RuntimeError("zygote failed to fork")
        conn.settimeout(0.0)  # non-blocking liveness probes
        return pid, conn
    except BaseException:
        conn.close()
        raise


def main() -> None:
    sock_path = os.environ["RAY_TPU_ZYGOTE_SOCKET"]
    # Preload the worker's import graph while still single-threaded.
    import ray_tpu._private.worker_main  # noqa: F401

    signal.signal(signal.SIGCHLD, signal.SIG_IGN)  # auto-reap children
    server = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    if os.path.exists(sock_path):
        os.unlink(sock_path)
    server.bind(sock_path)
    server.listen(64)
    # Tell the nodelet we're ready (it waits for the socket file).
    while True:
        try:
            conn, _ = server.accept()
        except InterruptedError:
            continue
        except OSError:
            return
        try:
            (ln,) = struct.unpack(">I", _recv_exact(conn, 4))
            req = pickle.loads(_recv_exact(conn, ln))
            pid = os.fork()
            if pid == 0:
                server.close()
                # Deliberately KEEP `conn` open: it is the nodelet's
                # liveness signal for this worker (EOF on worker death).
                _keep_alive.append(conn)
                _child(req)
                os._exit(0)  # unreachable (child runs the worker loop)
            conn.sendall(struct.pack(">q", pid))
        except Exception:
            try:
                conn.sendall(struct.pack(">q", -1))
            except OSError:
                pass
        finally:
            try:
                conn.close()  # parent's copy only; the child's stays open
            except OSError:
                pass


def _child(req: dict) -> None:
    os.setsid()
    env = req["env"]
    os.environ.clear()
    os.environ.update(env)
    # Freshly opened log file over stdout/stderr (line-buffered text).
    fd = os.open(req["log"], os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
    os.dup2(fd, 1)
    os.dup2(fd, 2)
    os.close(fd)
    sys.stdout = os.fdopen(1, "w", buffering=1)
    sys.stderr = os.fdopen(2, "w", buffering=1)
    signal.signal(signal.SIGCHLD, signal.SIG_DFL)
    # Config / logging derive from env: drop anything cached pre-fork.
    from ray_tpu.utils import config as _config_mod

    _config_mod._config = None
    # PYTHONPATH prepends (working_dir / py_modules) must reach THIS
    # interpreter's sys.path — there's no fresh interpreter start to do it.
    for p in reversed(env.get("PYTHONPATH", "").split(os.pathsep)):
        if p and p not in sys.path:
            sys.path.insert(0, p)
    try:
        from ray_tpu._private import worker_main

        worker_main.main()
    except BaseException:  # noqa: BLE001
        import traceback

        traceback.print_exc()
    finally:
        os._exit(0)


if __name__ == "__main__":
    main()
