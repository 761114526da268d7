"""Node bootstrap: start/locate GCS + nodelet processes for ray_tpu.init()
(reference: python/ray/_private/node.py:43 + services.py)."""

from __future__ import annotations

import atexit
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.accelerators import process_environ
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_port(host: str, port: int, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, port), timeout=1):
                return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"service at {host}:{port} did not come up")


_signal_nodes: List["Node"] = []
_signals_installed = False


def _register_signal_cleanup(node: "Node") -> None:
    """atexit does not run on SIGTERM/SIGINT-by-default, which leaks the
    daemon tree and its prefaulted shm arena. Install chaining handlers that
    shut nodes down, then re-deliver the signal (only in the main thread of
    the main interpreter; never overrides an application's own handler
    beyond chaining to it)."""
    global _signals_installed
    _signal_nodes.append(node)
    if _signals_installed:
        return
    import signal
    import threading

    if threading.current_thread() is not threading.main_thread():
        return

    def _make(prev):
        def _handler(signum, frame):
            for n in list(_signal_nodes):
                try:
                    n.shutdown()
                except Exception:
                    pass
            if callable(prev):
                prev(signum, frame)
            else:
                signal.signal(signum, signal.SIG_DFL)
                os.kill(os.getpid(), signum)

        return _handler

    try:
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev = signal.getsignal(sig)
            if prev is signal.SIG_IGN:
                continue
            signal.signal(sig, _make(None if prev in (signal.SIG_DFL, None)
                                     else prev))
        _signals_installed = True
    except (ValueError, OSError):  # non-main thread or restricted env
        pass


class Node:
    """Starts a head node's processes (GCS + one nodelet) as subprocesses and
    tears them down at exit."""

    def __init__(
        self,
        head: bool = True,
        gcs_address: Optional[Tuple[str, int]] = None,
        resources: Optional[Dict[str, float]] = None,
        object_store_memory: Optional[int] = None,
        session_dir: Optional[str] = None,
        node_name: str = "",
        labels: Optional[Dict[str, str]] = None,
    ):
        self.head = head
        self.session_id = f"session_{uuid.uuid4().hex[:12]}"
        self.session_dir = session_dir or os.path.join(
            tempfile.gettempdir(), "ray_tpu", self.session_id)
        os.makedirs(os.path.join(self.session_dir, "logs"), exist_ok=True)
        self.processes: List[subprocess.Popen] = []
        # GCS and nodelet never hold a chip: they count chips from device
        # files, and the nodelet hands leased workers their own TPU env.
        self._env = process_environ(os.environ)
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        self._env["PYTHONPATH"] = repo_root + os.pathsep + self._env.get(
            "PYTHONPATH", "")

        if head:
            gcs_port = free_port()
            self.gcs_address = ("127.0.0.1", gcs_port)
            self._gcs_cmd = [
                sys.executable, "-m", "ray_tpu.core.gcs",
                "--host", "127.0.0.1", "--port", str(gcs_port),
                "--persist-path",
                # sqlite → row-wise incremental writes (core/store_client.py);
                # a .pkl path selects the whole-snapshot pickle backend.
                os.path.join(self.session_dir, "gcs_store.sqlite"),
            ]
            self._gcs_proc = self._start_process(self._gcs_cmd, "gcs")
            _wait_port(*self.gcs_address)
        else:
            assert gcs_address is not None
            self.gcs_address = gcs_address

        nodelet_port = free_port()
        self.nodelet_address = ("127.0.0.1", nodelet_port)
        cmd = [
            sys.executable, "-m", "ray_tpu.core.nodelet",
            "--gcs-host", self.gcs_address[0],
            "--gcs-port", str(self.gcs_address[1]),
            "--port", str(nodelet_port),
            "--session-dir", self.session_dir,
            "--node-name", node_name,
        ]
        if resources is not None:
            cmd += ["--resources", json.dumps(resources)]
        if labels:
            cmd += ["--labels", json.dumps(labels)]
        if object_store_memory:
            cmd += ["--object-store-memory", str(object_store_memory)]
        self._start_process(cmd, f"nodelet-{node_name or 'head'}")
        _wait_port(*self.nodelet_address)
        self.store_path = self._wait_store_path()
        atexit.register(self.shutdown)
        _register_signal_cleanup(self)

    def restart_gcs(self, graceful: bool = False) -> None:
        """Kill the GCS process and start a fresh one on the same port with
        the same snapshot path (GCS fault-tolerance test hook; reference:
        Redis-backed GCS restart)."""
        assert self.head, "only the head node hosts the GCS"
        if graceful:
            self._gcs_proc.terminate()
        else:
            self._gcs_proc.kill()
        self._gcs_proc.wait()
        self.processes.remove(self._gcs_proc)
        self._gcs_proc = self._start_process(self._gcs_cmd, "gcs")
        _wait_port(*self.gcs_address)

    def _start_process(self, cmd: List[str], name: str) -> subprocess.Popen:
        log = open(os.path.join(self.session_dir, "logs", f"{name}.log"), "wb")
        proc = subprocess.Popen(cmd, env=self._env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        self.processes.append(proc)
        return proc

    def _wait_store_path(self, timeout: float = 30.0) -> str:
        """Ask the nodelet where its object store lives."""
        from ray_tpu._private.rpc import EventLoopThread, RpcClient

        loop = EventLoopThread("bootstrap")
        try:
            deadline = time.monotonic() + timeout
            while True:
                try:
                    client = RpcClient(*self.nodelet_address)
                    stats = loop.run(client.call("node_stats", timeout=5))
                    loop.run(client.close())
                    self.node_id = stats["node_id"]
                    return stats["store_path"]
                except Exception:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
        finally:
            loop.stop()

    def shutdown(self) -> None:
        for proc in reversed(self.processes):
            if proc.poll() is None:
                proc.terminate()
        # Grace must cover the nodelet's bounded teardown (worker reap +
        # server close + arena unlink) before escalating to SIGKILL.
        deadline = time.monotonic() + 10
        for proc in self.processes:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
        self.processes.clear()
