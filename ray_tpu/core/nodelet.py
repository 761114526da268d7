"""Nodelet — the per-node manager (raylet equivalent, SURVEY §2.1 C13–C20).

Owns: the node's shared-memory object store file, the worker pool (spawning /
reaping worker processes), local resource accounting + the lease protocol,
placement-group bundle prepare/commit, and heartbeats to GCS.

Redesign vs the reference raylet: no separate plasma server process (the store
is the mapped arena from shm_store.cc); leases are granted over the same RPC
plane; worker pushes happen directly submitter→worker so the nodelet stays off
the task hot path entirely (the reference also bypasses the raylet for actor
calls, but normal tasks flow through its dispatch queue — here a lease is a
worker address and the submitter talks to the worker directly, which is why
task throughput scales with submitters, not with the nodelet).
"""

from __future__ import annotations

import asyncio
import os
import signal
import socket as socket_mod
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.chaos import get_chaos
from ray_tpu._private.ids import NodeID, WorkerID
from ray_tpu._private.rpc import RpcClient, RpcServer
from ray_tpu._private.task_spec import ResourceSet
from ray_tpu.core.object_store import SharedMemoryStore
from ray_tpu.util import metrics as um
from ray_tpu.utils.config import get_config
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)


# Lease-path metric definitions — one site per metric (the registry dedupes
# by name; a second inline definition would silently drift).
def _m_leases_granted() -> "um.Counter":
    return um.get_counter("ray_tpu_leases_granted_total",
                          "Worker leases granted by this nodelet",
                          tag_keys=("node",))


def _m_leases_queued() -> "um.Counter":
    return um.get_counter("ray_tpu_leases_queued_total",
                          "Lease requests that had to wait for resources",
                          tag_keys=("node",))


def _m_sched_latency() -> "um.Histogram":
    return um.get_histogram(
        "ray_tpu_scheduling_latency_seconds",
        "Lease request arrival -> worker grant on this nodelet",
        tag_keys=("node",))


def _sweep_dead_arenas(shm_dir: str = "/dev/shm") -> int:
    """Unlink ray_tpu arenas whose owning nodelet is dead (a SIGKILL'd run
    leaks its arena with the full capacity committed — MADV_POPULATE pages).
    Ownership = sidecar <arena>.pid; no sidecar + old mtime = pre-crash
    leftover. Returns the number of arenas reclaimed."""
    reclaimed = 0
    try:
        names = os.listdir(shm_dir)
    except OSError:
        return 0
    now = time.time()
    for name in names:
        if not name.startswith("ray_tpu_") or name.endswith(".pid"):
            continue
        arena = os.path.join(shm_dir, name)
        pid_file = arena + ".pid"
        dead = False
        try:
            with open(pid_file) as f:
                pid = int(f.read().strip())
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                dead = True
            except PermissionError:
                pass  # alive, other user
        except (OSError, ValueError):
            # No/garbled sidecar: reclaim only if clearly stale.
            try:
                dead = now - os.path.getmtime(arena) > 300
            except OSError:
                continue
        if dead:
            for p in (arena, pid_file):
                try:
                    os.unlink(p)
                except OSError:
                    pass
            reclaimed += 1
            logger.info("reclaimed dead shm arena %s", arena)
    return reclaimed


class _ForkedProc:
    """subprocess.Popen-shaped handle over a zygote-forked worker.
    Liveness comes from the spawn connection the CHILD keeps open for its
    whole life (EOF ⇔ worker exited) — a bare pid probe would misread a
    recycled pid as a live worker after the zygote auto-reaps. Signals
    are only sent while the socket still shows the worker alive, which
    closes the signal-an-innocent-process window to the same EOF check."""

    def __init__(self, pid: int, liveness_sock):
        self.pid = pid
        self._sock = liveness_sock
        self._rc: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self._rc is not None:
            return self._rc
        try:
            if self._sock.recv(1, socket_mod.MSG_PEEK) == b"":
                self._mark_dead()
        except (BlockingIOError, InterruptedError):
            return None  # no data, connection open: worker alive
        except OSError:
            self._mark_dead()
        return self._rc

    def _mark_dead(self) -> None:
        self._rc = -1
        try:
            self._sock.close()
        except OSError:
            pass

    def terminate(self) -> None:
        if self.poll() is None:
            try:
                os.kill(self.pid, signal.SIGTERM)
            except ProcessLookupError:
                self._mark_dead()

    def kill(self) -> None:
        if self.poll() is None:
            try:
                os.kill(self.pid, signal.SIGKILL)
            except ProcessLookupError:
                self._mark_dead()

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("forked-worker",
                                                timeout or 0)
            time.sleep(0.02)
        return self._rc or 0


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, proc: subprocess.Popen,
                 env_key: str):
        self.worker_id = worker_id
        self.proc = proc
        self.env_key = env_key
        self.address: Optional[Tuple[str, int]] = None
        self.ready = asyncio.Event()
        self.leased = False
        self.lifetime = "task"  # or "actor"
        self.resources: Optional[ResourceSet] = None
        self.pg_bundle: Optional[Tuple[bytes, int]] = None
        self.last_idle = time.monotonic()
        self.tpu_chips: List[int] = []


class Nodelet:
    def __init__(
        self,
        gcs_address: Tuple[str, int],
        session_dir: str,
        host: str = "127.0.0.1",
        port: int = 0,
        resources: Optional[Dict[str, float]] = None,
        object_store_memory: Optional[int] = None,
        node_name: str = "",
        labels: Optional[Dict[str, str]] = None,
    ):
        self.node_id = NodeID.from_random()
        self.gcs_address = gcs_address
        self.session_dir = session_dir
        self.server = RpcServer(host, port)
        self.node_name = node_name or self.node_id.hex()[:8]
        # Node labels (reference: the static node labels label_selector.h
        # matches against); node_name always present for affinity UX.
        self.labels = {**(labels or {}), "node_name": self.node_name}
        # Per-node worker-log namespace (session_dir may be shared across
        # nodes on one filesystem).
        self._worker_log_dir = os.path.join(
            self.session_dir, "logs", self.node_id.hex()[:8])
        # shape-key -> (resources, last_seen): lease shapes this node
        # couldn't satisfy (autoscaler demand signal via heartbeat).
        self._unmet_demand: Dict[str, Tuple[Dict[str, float], float]] = {}

        from ray_tpu._private.accelerators import detect_resources

        self.resources_total = dict(resources or detect_resources())
        self.resources_available = dict(self.resources_total)
        # TPU chip accounting for visibility enforcement (reference:
        # _private/accelerators/tpu.py:110 TPU_VISIBLE_CHIPS): whole-chip
        # leases get disjoint chip ids; fractional leases share chip 0.
        self._tpu_chips_free = list(range(int(
            self.resources_total.get("TPU", 0))))
        cfg = get_config()
        store_capacity = object_store_memory or cfg.object_store_memory
        os.makedirs(session_dir, exist_ok=True)
        self.store_path = os.path.join(
            "/dev/shm", f"ray_tpu_{os.path.basename(session_dir)}_{self.node_name}"
        )
        _sweep_dead_arenas()
        if os.path.exists(self.store_path):
            os.unlink(self.store_path)
        self.store = SharedMemoryStore(self.store_path, capacity=store_capacity,
                                       create=True)
        # Ownership marker: lets a later nodelet's sweep reclaim this arena if
        # this process dies without running stop() (SIGKILL'd driver etc.).
        try:
            with open(self.store_path + ".pid", "w") as f:
                f.write(str(os.getpid()))
        except OSError:
            pass
        self.workers: Dict[WorkerID, WorkerHandle] = {}
        self._gcs: Optional[RpcClient] = None
        self._background: List[asyncio.Task] = []
        # Spilled objects materialized for chunked transfer: id -> (obj, ts).
        self._transfer_cache: Dict[bytes, Tuple[Any, float]] = {}
        self._lease_waiters: List[asyncio.Event] = []
        # pg bundles: (pg_id, bundle_index) -> {"resources": .., "state": ..}
        self._bundles: Dict[Tuple[bytes, int], Dict[str, Any]] = {}
        self._shutting_down = False
        # Preforked worker template (started on first plain-CPU spawn).
        self._zygote_proc: Optional[subprocess.Popen] = None
        self._zygote_sock: str = ""
        # Lease RPCs run _spawn_worker via run_in_executor: without this
        # lock two concurrent leases could each see _zygote_proc is None
        # and Popen two zygotes on one socket path (the second unlinks and
        # rebinds the first's socket, leaking the first process).
        self._zygote_lock = threading.Lock()
        # (last observed log-lease value, local monotonic time first seen)
        self._log_lease_seen: Tuple[Optional[bytes], float] = (None, 0.0)
        # Kernel-level worker memory containment (reference:
        # common/cgroup/): applied at lease time for leases that carry a
        # "memory" resource; no-op where the hierarchy isn't writable.
        from ray_tpu._private.cgroups import CgroupManager

        self._cgroups = (CgroupManager(self.node_id.hex()[:8])
                         if get_config().enable_worker_cgroups else None)
        # Versioned resource view (ray_syncer analog): bumped on every
        # availability/demand change, pushed by _resource_sync_loop.
        # The Event exists from construction so bumps before the sync
        # loop's first iteration are not lost to the heartbeat fallback.
        self._resource_version = 0
        self._sync_event = asyncio.Event()

    # ------------------------------------------------------------------
    async def start(self) -> Tuple[str, int]:
        for name in dir(self):
            if name.startswith("rpc_"):
                self.server.register(name[4:], getattr(self, name))
        addr = await self.server.start()
        self._gcs = RpcClient(*self.gcs_address, name="gcs")
        await self._gcs.call_retrying(
            "register_node",
            node_id=self.node_id.binary(),
            address=addr,
            resources=self.resources_total,
            object_store_path=self.store_path,
            labels=self.labels,
        )
        self._background.append(asyncio.ensure_future(self._heartbeat_loop()))
        self._background.append(
            asyncio.ensure_future(self._resource_sync_loop()))
        self._background.append(asyncio.ensure_future(self._reap_loop()))
        self._background.append(
            asyncio.ensure_future(self._memory_monitor_loop()))
        self._background.append(asyncio.ensure_future(self._log_monitor_loop()))
        # Metrics: this process has no Worker, so route registry flushes
        # through our own GCS client; the sampler loop feeds the per-node
        # gauges the Grafana cluster dashboard promises.
        loop = asyncio.get_running_loop()

        def _metrics_sink(key: str, payload: bytes) -> None:
            asyncio.run_coroutine_threadsafe(
                self._gcs.call("kv_put", key=key, value=payload), loop,
            ).result(timeout=10)

        um.set_flush_sink(_metrics_sink)
        self._background.append(asyncio.ensure_future(self._metrics_loop()))
        # Flight recorder: lag-sample this loop (worker loops attach in
        # EventLoopThread; the nodelet runs under asyncio.run).
        from ray_tpu._private import flight_recorder as _fr

        _fr.attach_loop(loop, "nodelet")
        logger.info("nodelet %s on %s:%d resources=%s", self.node_name, *addr,
                    self.resources_total)
        return addr

    async def stop(self) -> None:
        self._shutting_down = True
        for t in self._background:
            t.cancel()
        for w in list(self.workers.values()):
            if w.proc.poll() is None:
                w.proc.terminate()
        await asyncio.sleep(0)
        for w in list(self.workers.values()):
            try:
                w.proc.wait(timeout=2)
            except subprocess.TimeoutExpired:
                w.proc.kill()
        if self._zygote_proc is not None:
            try:
                self._zygote_proc.kill()
            except Exception:
                pass
            if self._zygote_sock and os.path.exists(self._zygote_sock):
                try:
                    os.unlink(self._zygote_sock)
                except OSError:
                    pass
        if self._gcs:
            await self._gcs.close()
        await self.server.stop()
        self.store.close()
        for p in (self.store_path, self.store_path + ".pid"):
            if os.path.exists(p):
                os.unlink(p)

    # ------------------------------------------------------------------
    # Log pipeline (reference: python/ray/_private/log_monitor.py — tail
    # worker log files → GCS pubsub → driver stdout)
    # ------------------------------------------------------------------
    async def _claim_component_log_lease(self, ttl: float
                                         ) -> Tuple[bool, bool]:
        """Refresh/claim the component-log-tailing lease. The value is
        (node_id, stamp) where the stamp exists only to make each refresh
        change the bytes: staleness is judged by observing the VALUE
        unchanged for ttl of LOCAL monotonic time, never by comparing a
        remote wall-clock stamp against ours (cross-node clock skew > ttl
        would otherwise create dueling leaders / premature takeover —
        ADVICE r4). kv_cas makes the takeover atomic under concurrent
        claimants. Returns (leader, took_over): took_over means the key
        previously named another node, so history already published by the
        old leader must not be re-shipped."""
        import pickle

        key = "logtail:component_leader"
        me = self.node_id.binary()
        cur = await self._gcs.call("kv_get", key=key)
        owner: Optional[bytes] = None
        if cur:
            try:
                owner, _ = pickle.loads(cur)
            except Exception:
                pass  # legacy/undecodable: stale once it stops changing
        now_m = time.monotonic()
        if cur is not None and owner != me:
            seen_val, seen_at = self._log_lease_seen
            if seen_val != cur:
                # value moved since our last probe: holder is alive
                self._log_lease_seen = (cur, now_m)
                return False, False
            if now_m - seen_at <= ttl:
                return False, False
        new = pickle.dumps((me, time.time()))
        won = bool(await self._gcs.call("kv_cas", key=key,
                                        expect=cur, value=new))
        if won:
            self._log_lease_seen = (new, now_m)
        return won, won and cur is not None and owner != me

    async def _log_monitor_loop(self) -> None:
        # Tail only THIS node's worker logs. Multi-node clusters sharing one
        # filesystem (cluster_utils, fake TPU-pod transport) would otherwise
        # have N nodelets each republishing every worker's output with the
        # wrong node label. Component logs (gcs.log, nodelet-*.log) live at
        # the top level of the shared logs dir; exactly one nodelet holds a
        # LEASED kv key for them (timestamp refreshed while alive) so that a
        # dead leader — or stale node ids left in a persistent sqlite-backed
        # store across cluster restarts — is replaced instead of orphaning
        # component-log tailing forever.
        log_dir = self._worker_log_dir
        component_dir = ""
        lease_ttl = 10.0
        next_lease_at = 0.0
        offsets: Dict[str, int] = {}
        partial: Dict[str, bytes] = {}
        while not self._shutting_down:
            await asyncio.sleep(0.5)
            try:
                now = time.time()
                if self._gcs is not None and now >= next_lease_at:
                    leader, took_over = (
                        await self._claim_component_log_lease(lease_ttl))
                    component_dir = (os.path.join(self.session_dir, "logs")
                                     if leader else "")
                    if took_over and component_dir:
                        # Start tailing at the CURRENT end of each component
                        # file: the dead leader already published history,
                        # and re-shipping it would duplicate driver output.
                        for n in sorted(os.listdir(component_dir)):
                            p = os.path.join(component_dir, n)
                            if os.path.isfile(p) and p not in offsets:
                                try:
                                    offsets[p] = os.path.getsize(p)
                                except OSError:
                                    pass
                    # Holders refresh well inside the ttl; others probe at
                    # ttl pace so takeover happens within ~2 ttl.
                    next_lease_at = now + (lease_ttl / 3 if leader
                                           else lease_ttl)
                names = [
                    (log_dir, n)
                    for n in (sorted(os.listdir(log_dir))
                              if os.path.isdir(log_dir) else [])]
                if component_dir:
                    names += [
                        (component_dir, n)
                        for n in sorted(os.listdir(component_dir))
                        if os.path.isfile(os.path.join(component_dir, n))]
                batches = []
                for dirpath, name in names:
                    if not name.endswith(".log"):
                        continue
                    path = os.path.join(dirpath, name)
                    try:
                        size = os.path.getsize(path)
                    except OSError:
                        continue
                    pos = offsets.get(path, 0)
                    if size <= pos:
                        continue
                    with open(path, "rb") as f:
                        f.seek(pos)
                        chunk = partial.pop(path, b"") + f.read(
                            min(size - pos, 512 * 1024))
                        offsets[path] = f.tell()
                    *lines, rest = chunk.split(b"\n")
                    if rest:
                        partial[path] = rest
                    lines = [ln.decode("utf-8", "replace") for ln in lines
                             if ln.strip()]
                    # Ship everything read (offsets already advanced past
                    # it) — in capped batches, never by dropping.
                    for j in range(0, len(lines), 200):
                        batches.append({
                            "source": name[:-len(".log")],
                            "node": self.node_name,
                            "lines": lines[j:j + 200],
                        })
                if batches and self._gcs is not None:
                    await self._gcs.notify(
                        "publish", channel="logs", message=batches)
            except asyncio.CancelledError:
                raise
            except Exception:
                pass  # log shipping must never hurt the node

    # ------------------------------------------------------------------
    # Worker pool (reference: worker_pool.h:283)
    # ------------------------------------------------------------------
    def _spawn_worker(self, env_key: str,
                      runtime_env: Optional[Dict[str, Any]],
                      needs_tpu: bool = False,
                      tpu_chips: Optional[List[int]] = None,
                      env_updates: Optional[Dict[str, str]] = None
                      ) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        from ray_tpu._private.accelerators import process_environ

        # Only a TPU lease sees chips (reference: TPU_VISIBLE_CHIPS in
        # accelerators/tpu.py:110); every other worker is a CPU process.
        env = process_environ({**os.environ, **(env_updates or {})},
                              tpu_chips if needs_tpu else ())
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        env["RAY_TPU_NODELET_ADDR"] = f"{self.server.host}:{self.server.port}"
        env["RAY_TPU_GCS_ADDR"] = f"{self.gcs_address[0]}:{self.gcs_address[1]}"
        env["RAY_TPU_STORE_PATH"] = self.store_path
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["RAY_TPU_NODE_NAME"] = self.node_name
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        prepend = env.pop("RAY_TPU_PYTHONPATH_PREPEND", "")
        if prepend:
            env["PYTHONPATH"] = prepend + os.pathsep + env["PYTHONPATH"]
        if runtime_env:
            for k, v in (runtime_env.get("env_vars") or {}).items():
                env[k] = v
        log_dir = self._worker_log_dir
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(log_dir, f"worker-{worker_id.hex()[:8]}.log")
        # pip/uv runtime envs run the worker under their venv's interpreter
        # (reference: runtime_env/pip.py py_executable override).
        python = env.pop("RAY_TPU_PYTHON_EXECUTABLE", sys.executable)
        # Fast path: plain CPU workers fork from the preforked zygote
        # (~ms instead of ~0.6s interpreter+import start). TPU workers
        # need a fresh interpreter (libtpu reads its chip bounds from the
        # environment the process starts with), and custom interpreters /
        # runtime envs take the classic spawn.
        proc: Any = None
        if (not needs_tpu and python == sys.executable
                and not runtime_env):
            forked = self._spawn_from_zygote(env, log_path)
            if forked is not None:
                proc = _ForkedProc(*forked)
        if proc is None:
            out = open(log_path, "wb")
            proc = subprocess.Popen(
                [python, "-m", "ray_tpu._private.worker_main"],
                env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        handle = WorkerHandle(worker_id, proc, env_key)
        self.workers[worker_id] = handle
        return handle

    def _spawn_from_zygote(self, env: Dict[str, str], log_path: str
                           ) -> Optional[Tuple[int, Any]]:
        """Fork a worker from the zygote, starting it on first use.
        Returns None (→ classic spawn) when the zygote is unavailable."""
        from ray_tpu._private.zygote import spawn_via_zygote

        with self._zygote_lock:
            if (self._zygote_proc is not None
                    and self._zygote_proc.poll() is not None):
                self._zygote_proc = None  # died: restart on next spawn
            if self._zygote_proc is None:
                sock = os.path.join(self.session_dir,
                                    f"zygote-{self.node_id.hex()[:8]}.sock")
                from ray_tpu._private.accelerators import process_environ

                zenv = process_environ(os.environ)
                zenv["RAY_TPU_ZYGOTE_SOCKET"] = sock
                repo_root = os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))))
                zenv["PYTHONPATH"] = (repo_root + os.pathsep
                                      + zenv.get("PYTHONPATH", ""))
                self._zygote_sock = sock
                self._zygote_proc = subprocess.Popen(
                    [sys.executable, "-m", "ray_tpu._private.zygote"],
                    env=zenv, start_new_session=True)
                deadline = time.monotonic() + 20.0
                while (not os.path.exists(sock)
                       and time.monotonic() < deadline
                       and self._zygote_proc.poll() is None):
                    time.sleep(0.01)
        try:
            get_chaos().failpoint("nodelet.zygote_fork")
            return spawn_via_zygote(self._zygote_sock, env, log_path)
        except Exception:
            logger.warning("zygote spawn failed; falling back to exec",
                           exc_info=True)
            return None

    async def rpc_register_worker(
        self, worker_id: bytes, address: Tuple[str, int]
    ) -> Dict[str, Any]:
        """Called by a freshly-started worker process."""
        wid = WorkerID(worker_id)
        handle = self.workers.get(wid)
        if handle is None:
            return {"ok": False}
        handle.address = tuple(address)
        handle.ready.set()
        return {"ok": True}

    async def _get_idle_worker(
        self, env_key: str, runtime_env: Optional[Dict[str, Any]],
        needs_tpu: bool = False, tpu_chips: Optional[List[int]] = None,
    ) -> WorkerHandle:
        """Returns a worker already marked leased — reserving at selection
        time closes the race where two lease requests pick the same worker
        (one scanning the pool while the other awaits its spawned worker's
        ready event)."""
        for w in self.workers.values():
            if (not w.leased and w.env_key == env_key and w.ready.is_set()
                    and w.proc.poll() is None):
                w.leased = True
                self._maybe_prewarm(env_key)
                return w
        env_updates: Dict[str, str] = {}
        if runtime_env and (runtime_env.get("working_dir")
                            or runtime_env.get("py_modules")
                            or runtime_env.get("pip")
                            or runtime_env.get("uv")):
            from ray_tpu._private.runtime_env import materialize

            env_updates = await materialize(
                runtime_env, self._gcs,
                os.path.join(self.session_dir, "runtime_envs"))
        # Off-loop: the zygote round trip (and its one-time ~0.6s startup)
        # and Popen() must not stall RPC/heartbeat handling.
        handle = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self._spawn_worker(
                env_key, runtime_env, needs_tpu, tpu_chips, env_updates))
        handle.leased = True
        self._maybe_prewarm(env_key)
        try:
            await asyncio.wait_for(handle.ready.wait(),
                                   get_config().worker_start_timeout_s)
        except BaseException:
            handle.leased = False
            if handle.proc.poll() is None:
                handle.proc.terminate()
            self.workers.pop(handle.worker_id, None)
            raise
        return handle

    def _maybe_prewarm(self, env_key: str) -> None:
        """Keep a small reserve of BOOTED plain-CPU workers ahead of
        demand (reference: the WorkerPool's prestarted python workers).
        Forking + boot (~10-20 ms each) then happens in the background
        between lease waves instead of on the bring-up critical path —
        actor/worker churn overlaps its spawn cost with driver-side work."""
        cfg = get_config()
        if env_key != "" or cfg.worker_prewarm <= 0:
            return  # only the vanilla pool is predictably reusable
        if self.__dict__.get("_prewarming"):
            return
        idle = sum(1 for w in self.workers.values()
                   if not w.leased and w.env_key == ""
                   and w.proc.poll() is None)
        want = min(cfg.worker_prewarm - idle,
                   max(0, cfg.worker_pool_max - len(self.workers)))
        if want <= 0:
            return
        self.__dict__["_prewarming"] = True

        async def _replenish(n: int) -> None:
            loop = asyncio.get_running_loop()
            try:
                for _ in range(n):
                    try:
                        await loop.run_in_executor(
                            None, lambda: self._spawn_worker(
                                "", None, False, None, {}))
                    except Exception:
                        return  # zygote down / spawn failing: stop quietly
            finally:
                self.__dict__["_prewarming"] = False

        asyncio.ensure_future(_replenish(want))

    # ------------------------------------------------------------------
    # Leases (reference: RequestWorkerLease node_manager.proto:394 +
    # LocalTaskManager dispatch)
    # ------------------------------------------------------------------
    async def rpc_lease_worker(
        self,
        resources: Dict[str, float],
        runtime_env: Optional[Dict[str, Any]] = None,
        lifetime: str = "task",
        pg_bundle: Optional[Tuple[bytes, int]] = None,
        block: bool = True,
        owner: Optional[List[Any]] = None,
    ) -> Dict[str, Any]:
        req = ResourceSet(resources)
        num_tpus = float(resources.get("TPU", 0) or 0)
        needs_tpu = num_tpus > 0
        env_key = repr(sorted((runtime_env or {}).items())) + (
            "|tpu" if needs_tpu else "")
        cfg = get_config()
        t_req = time.monotonic()
        queued_counted = False
        deadline = time.monotonic() + cfg.worker_start_timeout_s
        while True:
            pool = self._bundle_pool(pg_bundle)
            if pool is None:
                return {"ok": False, "error": "unknown placement bundle"}
            if req.fits_in(pool):
                # Failpoint BEFORE any accounting mutates: an injected
                # grant failure/delay must never leak reserved resources.
                # The await yields the loop, so re-check fitness after —
                # a concurrent grant may have taken the resources.
                chaos = get_chaos()
                if chaos.enabled:
                    await chaos.failpoint_async("nodelet.lease_grant")
                    if not req.fits_in(pool):
                        continue
                req.subtract_from(pool)
                self._bump_resources()
                # Disjoint chip assignment per whole-chip lease; fractional
                # leases share chip 0 (reference: tpu.py visibility).
                chips: List[int] = []
                if needs_tpu:
                    if num_tpus >= 1 and self._tpu_chips_free:
                        chips = sorted(self._tpu_chips_free[-int(num_tpus):])
                        del self._tpu_chips_free[-int(num_tpus):]
                    else:
                        chips = [0]
                    env_key += f"|chips:{','.join(map(str, chips))}"
                try:
                    worker = await self._get_idle_worker(env_key, runtime_env,
                                                         needs_tpu, chips)
                except Exception as e:
                    req.add_to(pool)
                    self._bump_resources()  # rollback must sync too, or
                    # the GCS under-schedules this node for a heartbeat
                    if num_tpus >= 1:
                        self._tpu_chips_free.extend(chips)
                    return {"ok": False, "error": f"worker start failed: {e!r}"}
                worker.leased = True
                worker.lifetime = lifetime
                worker.lease_owner = tuple(owner) if owner else None
                worker.resources = req
                mem = float(resources.get("memory", 0) or 0)
                if mem > 0 and self._cgroups is not None                         and self._cgroups.available:
                    worker.cgroup_limited = self._cgroups.limit_worker(
                        worker.worker_id.hex()[:12], worker.proc.pid,
                        int(mem))
                worker.pg_bundle = pg_bundle
                worker.tpu_chips = chips if num_tpus >= 1 else []
                _m_leases_granted().inc(tags={"node": self.node_name})
                _m_sched_latency().observe(time.monotonic() - t_req,
                                           tags={"node": self.node_name})
                return {
                    "ok": True,
                    "worker_id": worker.worker_id.binary(),
                    "worker_address": worker.address,
                    "node_id": self.node_id.binary(),
                    # Other lease requests are parked on this node RIGHT
                    # NOW: the grantee's pump must not linger-hold the
                    # worker when its queue idles (a 0.2 s idle hold per
                    # rotation starves contending submitters ~5x on a
                    # worker-starved node).
                    "contended": bool(self._lease_waiters),
                }
            if not queued_counted:
                queued_counted = True
                _m_leases_queued().inc(tags={"node": self.node_name})
            if not block:
                if pg_bundle is None:
                    # PG-bundle leases are pinned to this node; a new node
                    # could never satisfy them (pending-PG demand is
                    # counted separately by the autoscaler).
                    self._record_unmet_demand(resources)
                return {"ok": False, "error": "resources unavailable",
                        "retry": True}
            if time.monotonic() > deadline:
                if pg_bundle is None:
                    self._record_unmet_demand(resources)
                return {"ok": False, "error": "lease timeout", "retry": True}
            event = asyncio.Event()
            self._lease_waiters.append(event)
            try:
                await asyncio.wait_for(event.wait(), 1.0)
            except asyncio.TimeoutError:
                pass
            finally:
                if event in self._lease_waiters:
                    self._lease_waiters.remove(event)

    def _bundle_pool(self, pg_bundle) -> Optional[Dict[str, float]]:
        if pg_bundle is None:
            return self.resources_available
        entry = self._bundles.get((bytes(pg_bundle[0]), int(pg_bundle[1])))
        if entry is None or entry["state"] != "committed":
            return None
        return entry["available"]

    async def rpc_return_worker(
        self, worker_id: bytes, kill: bool = False
    ) -> Dict[str, Any]:
        wid = WorkerID(worker_id)
        worker = self.workers.get(wid)
        if worker is None:
            return {"ok": False}
        if worker.resources is not None:
            pool = self._bundle_pool(getattr(worker, "pg_bundle", None))
            if pool is not None:
                worker.resources.add_to(pool)
            worker.resources = None
        if worker.tpu_chips:
            self._tpu_chips_free.extend(worker.tpu_chips)
            worker.tpu_chips = []
        worker.leased = False
        worker.last_idle = time.monotonic()
        if getattr(worker, "cgroup_limited", False)                 and self._cgroups is not None:
            self._cgroups.relax_worker(worker.worker_id.hex()[:12])
            worker.cgroup_limited = False
        self._wake_lease_waiters()
        if kill and worker.proc.poll() is None:
            worker.proc.terminate()
        return {"ok": True}

    def _wake_lease_waiters(self) -> None:
        for event in self._lease_waiters:
            event.set()
        self._bump_resources()

    # ------------------------------------------------------------------
    # Resource syncer (reference: common/ray_syncer — versioned resource
    # views pushed on CHANGE over a bidi stream, not polled; here a
    # debounced push RPC with a monotonic version, with the heartbeat as
    # the liveness carrier and periodic full-snapshot fallback)
    # ------------------------------------------------------------------
    def _bump_resources(self) -> None:
        """Mark the resource view dirty: bumps the version and kicks the
        sync loop so the GCS sees the change within the debounce window
        (~50 ms), not a heartbeat period later."""
        self._resource_version += 1
        self._sync_event.set()

    async def _resource_sync_loop(self) -> None:
        while not self._shutting_down:
            try:
                await self._sync_event.wait()
                await asyncio.sleep(0.05)  # debounce bursts of changes
                self._sync_event.clear()
                version = self._resource_version
                await self._gcs.call(
                    "sync_resources",
                    node_id=self.node_id.binary(),
                    version=version,
                    resources_available=dict(self.resources_available),
                    demand=self._demand_snapshot(),
                )
            except asyncio.CancelledError:
                return
            except Exception:
                # Dropped sync: the next change or heartbeat (which also
                # carries the version) re-converges the view.
                await asyncio.sleep(0.5)

    # ------------------------------------------------------------------
    # Placement group bundles: 2-phase prepare/commit (reference:
    # placement_group_resource_manager.h:50,90)
    # ------------------------------------------------------------------
    async def rpc_prepare_bundle(
        self, pg_id: bytes, bundle_index: int, resources: Dict[str, float]
    ) -> Dict[str, Any]:
        req = ResourceSet(resources)
        if not req.fits_in(self.resources_available):
            return {"ok": False, "error": "insufficient resources"}
        req.subtract_from(self.resources_available)
        self._bump_resources()
        self._bundles[(pg_id, bundle_index)] = {
            "resources": dict(req), "available": dict(req), "state": "prepared",
        }
        return {"ok": True}

    async def rpc_commit_bundle(self, pg_id: bytes,
                                bundle_index: int) -> Dict[str, Any]:
        entry = self._bundles.get((pg_id, bundle_index))
        if entry is None:
            return {"ok": False}
        entry["state"] = "committed"
        self._wake_lease_waiters()
        return {"ok": True}

    async def rpc_return_bundle(self, pg_id: bytes,
                                bundle_index: int) -> Dict[str, Any]:
        entry = self._bundles.pop((pg_id, bundle_index), None)
        if entry is not None:
            ResourceSet(entry["resources"]).add_to(self.resources_available)
            self._wake_lease_waiters()
        return {"ok": True}

    # ------------------------------------------------------------------
    # Introspection / state API support
    # ------------------------------------------------------------------
    async def rpc_node_stats(self) -> Dict[str, Any]:
        return {
            "node_id": self.node_id.binary(),
            "node_name": self.node_name,
            "resources_total": self.resources_total,
            "resources_available": dict(self.resources_available),
            "num_workers": len(self.workers),
            "num_leased": sum(1 for w in self.workers.values() if w.leased),
            "workers": [
                {
                    "worker_id": w.worker_id.hex(),
                    "pid": w.proc.pid,
                    "leased": w.leased,
                    "lifetime": w.lifetime,
                    "address": w.address,
                    "tpu_chips": list(w.tpu_chips),
                }
                for w in self.workers.values()
            ],
            "store": self.store.stats(),
            "store_path": self.store_path,
            "bundles": {
                f"{k[0].hex()[:8]}:{k[1]}": v["state"]
                for k, v in self._bundles.items()
            },
        }

    def _read_object_for_transfer(self, object_id: bytes):
        """Sealed object lookup (shm, then spill) shared by the whole-object
        and chunked fetch paths. Shm reads are cheap memoryviews; a SPILLED
        object materializes from disk, so a chunked pull must not re-read
        the whole file per chunk — recently-materialized spilled objects are
        held in a tiny TTL cache for the duration of the transfer."""
        from ray_tpu._private.ids import ObjectID

        oid = ObjectID(object_id)
        obj = self.store.get_serialized(oid)
        if obj is not None:
            return obj
        now = time.monotonic()
        cached = self._transfer_cache.get(object_id)
        if cached is not None and now - cached[1] < 30.0:
            self._transfer_cache[object_id] = (cached[0], now)
            return cached[0]
        from ray_tpu.core.object_store import spill_read

        obj = spill_read(os.path.join(
            self.session_dir, "spill", self.node_id.hex()), oid)
        if obj is not None:
            self._transfer_cache[object_id] = (obj, now)
            # Evict stale entries so the cache never outgrows one or two
            # in-flight transfers.
            for k in [k for k, (_, ts) in self._transfer_cache.items()
                      if now - ts > 30.0]:
                self._transfer_cache.pop(k, None)
        return obj

    async def rpc_fetch_object_info(
            self, object_id: bytes,
            inline_below: int = 0) -> Optional[Dict[str, Any]]:
        """Chunked-pull step 1: sizes, so the puller can plan chunk ranges
        and apply admission control (reference: PullManager learns object
        sizes before activating pulls, pull_manager.h:49). Objects at or
        under `inline_below` come back whole in this same reply — the
        common small-object fetch stays one RPC."""
        obj = self._read_object_for_transfer(object_id)
        if obj is None:
            return None
        sizes = [len(b) for b in obj.buffers]
        if inline_below and sum(sizes) <= inline_below:
            return {
                "metadata": bytes(obj.metadata),
                "sizes": sizes,
                "buffers": [bytes(b) for b in obj.buffers],
            }
        return {"metadata": bytes(obj.metadata), "sizes": sizes}

    # Peer-serving directory: object id -> chunk offset -> puller worker
    # addresses known (from pull acks) to hold that chunk. Bounded; a
    # stale entry just costs the redirected puller one fallback RPC.
    _CHUNK_DIR_MAX_OBJECTS = 16

    def _learn_chunk_locations(self, object_id: bytes, puller, have) -> None:
        if not puller or not have:
            return
        directory = self.__dict__.setdefault("_chunk_dir", {})
        if object_id not in directory \
                and len(directory) >= self._CHUNK_DIR_MAX_OBJECTS:
            directory.pop(next(iter(directory)))
        entry = directory.setdefault(object_id, {})
        addr = tuple(puller)
        for off in have:
            holders = entry.setdefault(int(off), [])
            if addr not in holders:
                holders.append(addr)

    def _chunk_redirect(self, object_id: bytes, offset: int,
                        puller) -> Optional[List[Any]]:
        """When another puller already holds this chunk, alternate between
        serving bytes and handing out the peer's address — the owner
        becomes a distribution-tree ROOT serving ~half the load while
        peers fan out the rest (reference: push_manager.h:27 /
        pull_manager.h:49). The 50/50 split self-balances on a node that
        is the sole source: redirecting everything would idle the owner's
        own bandwidth."""
        if not puller:
            return None
        entry = self.__dict__.get("_chunk_dir", {}).get(object_id)
        if not entry:
            return None
        holders = [a for a in entry.get(int(offset), ())
                   if a != tuple(puller)]
        if not holders:
            return None
        rr = self.__dict__.get("_redir_rr", 0) + 1
        self.__dict__["_redir_rr"] = rr
        if rr % 2 == 0:
            return None  # owner serves this one directly
        return list(holders[rr % len(holders)])

    async def rpc_fetch_object_chunk(
            self, object_id: bytes, offset: int, length: int,
            puller: Optional[List[Any]] = None,
            have: Optional[List[int]] = None,
            no_redirect: bool = False) -> Optional[Dict[str, Any]]:
        """Chunked-pull step 2: one slice of the logical concatenation of
        the object's buffers (reference: ObjectManager chunked Push/Pull,
        object_buffer_pool.h). The slice ships as a pickle-5 out-of-band
        buffer: when it falls inside one source buffer (the common case —
        one numpy payload) it is a zero-copy view of the shm arena all the
        way to the socket (the view holds the arena read pin); spans are
        assembled once into a bytearray, still oob on the wire.

        `puller`+`have` piggyback the caller's landed chunks (pull acks);
        under concurrent pressure the reply may be {"redirect": addr}
        pointing at a peer that holds the chunk (no_redirect forces
        bytes — the fallback after a failed peer fetch)."""
        self._learn_chunk_locations(object_id, puller, have)
        if not no_redirect:
            redirect = self._chunk_redirect(object_id, offset, puller)
            if redirect is not None:
                return {"redirect": redirect}
        return await self._serve_chunk(object_id, offset, length)

    async def _serve_chunk(self, object_id: bytes, offset: int,
                           length: int) -> Optional[Dict[str, Any]]:
        import pickle

        obj = self._read_object_for_transfer(object_id)
        if obj is None:
            return None
        spans = []
        pos = 0
        for buf in obj.buffers:
            n = len(buf)
            if pos + n <= offset:
                pos += n
                continue
            start = max(0, offset - pos)
            take = min(n - start, offset + length - (pos + start))
            if take > 0:
                spans.append(memoryview(buf)[start:start + take])
            pos += n
            if sum(len(s) for s in spans) >= length:
                break
        if len(spans) == 1:
            return {"data": pickle.PickleBuffer(spans[0])}
        out = bytearray()
        for s in spans:
            out += s
        return {"data": pickle.PickleBuffer(out)}

    async def rpc_ping(self) -> str:
        return "pong"

    # ------------------------------------------------------------------
    # Profiling / debugging endpoints (reference: the per-node dashboard
    # agent's reporter module — py-spy stack dumps and psutil process
    # stats, dashboard/modules/reporter/; here native: sys._current_frames
    # in-worker and /proc sampling here)
    # ------------------------------------------------------------------
    async def _fanout_workers(self, method: str, *, timeout: float = 10.0,
                              worker_id_prefix: str = "",
                              **kwargs) -> Dict[str, Any]:
        """Call one RPC on every live worker concurrently, error-wrapped
        per worker (shared scaffolding for the reporter endpoints)."""

        async def _one(wid, w):
            client = None
            try:
                client = RpcClient(*w.address, name=method)
                return wid.hex()[:12], await client.call(
                    method, timeout=timeout, **kwargs)
            except Exception as e:  # noqa: BLE001
                return wid.hex()[:12], {"error": repr(e)}
            finally:
                if client is not None:
                    try:
                        await client.close()
                    except Exception:
                        pass

        targets = [(wid, w) for wid, w in list(self.workers.items())
                   if w.proc.poll() is None and w.address is not None
                   and wid.hex().startswith(worker_id_prefix)]
        pairs = await asyncio.gather(*[_one(wid, w) for wid, w in targets])
        return {"node": self.node_name, "workers": dict(pairs)}

    async def rpc_node_stacks(self) -> Dict[str, Any]:
        """All-thread python stacks for every live worker on this node,
        gathered concurrently (the `ray stack` surface)."""
        return await self._fanout_workers("dump_stacks")

    async def rpc_node_overhead(self) -> Dict[str, Any]:
        """Sampled per-call overhead decomposition from every live worker
        on this node (flight recorder; `ray_tpu profile --overhead`)."""
        return await self._fanout_workers("overhead_breakdown")

    async def rpc_node_flight_record(self) -> Dict[str, Any]:
        """Flight-recorder ring dumps: every live worker's, plus this
        nodelet's own (`ray_tpu debug flight-record`)."""
        from ray_tpu._private import flight_recorder as _fr

        out = await self._fanout_workers("flight_record")
        out["nodelet"] = _fr.flight_snapshot()
        return out

    async def rpc_profile_workers(self, kind: str = "cpu",
                                  duration: float = 5.0,
                                  hz: float = 99.0,
                                  worker_id_prefix: str = "",
                                  top: int = 50) -> Dict[str, Any]:
        """Run the sampling CPU profiler (kind="cpu" → folded stacks) or
        the tracemalloc heap profiler (kind="heap") inside this node's
        workers, concurrently (reference: reporter agent py-spy/memray
        endpoints, dashboard/modules/reporter/). worker_id_prefix narrows
        to one worker; default profiles every live worker on the node."""
        method = "cpu_profile" if kind == "cpu" else "heap_profile"
        kwargs = ({"duration": duration, "hz": hz} if kind == "cpu"
                  else {"duration": duration, "top": top})
        return await self._fanout_workers(
            method, timeout=duration + 30,
            worker_id_prefix=worker_id_prefix, **kwargs)

    async def rpc_node_proc_stats(self) -> Dict[str, Any]:
        """Per-worker process stats from /proc (cpu seconds, rss, threads)
        plus the nodelet's own — the reporter-agent metrics floor."""
        out: Dict[str, Any] = {"node": self.node_name, "procs": {}}
        pids = {"nodelet": os.getpid()}
        for wid, w in list(self.workers.items()):
            if w.proc.poll() is None:
                pids[wid.hex()[:12]] = w.proc.pid
        page = os.sysconf("SC_PAGE_SIZE")
        tick = os.sysconf("SC_CLK_TCK")
        for label, pid in pids.items():
            try:
                with open(f"/proc/{pid}/stat") as f:
                    parts = f.read().rsplit(")", 1)[1].split()
                utime, stime = int(parts[11]), int(parts[12])
                threads = int(parts[17])
                with open(f"/proc/{pid}/statm") as f:
                    rss_pages = int(f.read().split()[1])
                out["procs"][label] = {
                    "pid": pid,
                    "cpu_seconds": round((utime + stime) / tick, 2),
                    "rss_mb": round(rss_pages * page / 2**20, 1),
                    "num_threads": threads,
                }
            except OSError:
                pass
        return out

    # ------------------------------------------------------------------
    # Background loops
    # ------------------------------------------------------------------
    def _record_unmet_demand(self, resources: Dict[str, float]) -> None:
        """Resource shapes this node could not lease — carried on the next
        heartbeat so the autoscaler sees TASK demand, not just pending
        actors/PGs (reference: resource_demand in the load report,
        raylet's ResourceLoad)."""
        key = repr(sorted(resources.items()))
        self._unmet_demand[key] = (dict(resources), time.monotonic())
        self._bump_resources()

    def _demand_snapshot(self) -> List[Dict[str, float]]:
        cutoff = time.monotonic() - 30.0
        for key, (_, ts) in list(self._unmet_demand.items()):
            if ts < cutoff:
                del self._unmet_demand[key]
        return [shape for shape, _ in self._unmet_demand.values()]

    async def _metrics_loop(self) -> None:
        """Per-node runtime gauges (reference: the reporter agent's psutil
        sampling -> OpenCensus gauges): resource availability, leased
        workers, object-store usage, and per-worker RSS. Labelled gauges
        are cleared each round so series for dead workers don't linger."""
        node = self.node_name
        g_avail = um.get_gauge(
            "ray_tpu_resource_available",
            "Schedulable capacity currently available on the node",
            tag_keys=("node", "resource"))
        g_leased = um.get_gauge(
            "ray_tpu_workers_leased",
            "Worker processes currently leased out on the node",
            tag_keys=("node",))
        g_workers = um.get_gauge(
            "ray_tpu_workers_alive",
            "Worker processes alive in the node's pool",
            tag_keys=("node",))
        g_store = um.get_gauge(
            "ray_tpu_object_store_bytes_in_use",
            "Bytes resident in the node's shared-memory object store",
            tag_keys=("node",))
        g_rss = um.get_gauge(
            "ray_tpu_worker_rss_mb",
            "Resident set size of each live worker process (MiB)",
            tag_keys=("node", "worker"))
        # Pre-register the node's counters/histograms at zero so every
        # dashboard-promised series exists from node start, not from the
        # first lease / first spill.
        from ray_tpu.core.object_store import (
            _arena_puts_counter,
            _spilled_bytes_counter,
            _spilled_objects_counter,
        )

        _m_leases_granted().inc(0, tags={"node": node})
        _m_leases_queued().inc(0, tags={"node": node})
        _m_sched_latency()
        _spilled_objects_counter().inc(0)
        _spilled_bytes_counter().inc(0)
        _arena_puts_counter()
        page = os.sysconf("SC_PAGE_SIZE")
        while not self._shutting_down:
            await asyncio.sleep(2.0)
            try:
                g_avail.set_many(
                    [({"node": node, "resource": res}, v)
                     for res, v in dict(self.resources_available).items()])
                live = [(wid, w) for wid, w in list(self.workers.items())
                        if w.proc.poll() is None]
                g_leased.set(sum(1 for _, w in live if w.leased),
                             tags={"node": node})
                g_workers.set(len(live), tags={"node": node})
                try:
                    g_store.set(
                        float(self.store.stats().get("bytes_in_use", 0)),
                        tags={"node": node})
                except Exception:
                    pass
                rss_items = []
                for wid, w in live:
                    try:
                        with open(f"/proc/{w.proc.pid}/statm") as f:
                            rss_pages = int(f.read().split()[1])
                    except (OSError, ValueError, IndexError):
                        continue
                    rss_items.append((
                        {"node": node, "worker": wid.hex()[:12]},
                        round(rss_pages * page / 2**20, 1)))
                # Atomic replace: dead workers' series drop without a
                # clear-then-set window a concurrent flush could snapshot.
                g_rss.set_many(rss_items)
            except asyncio.CancelledError:
                raise
            except Exception:
                pass  # sampling must never hurt the node

    async def _heartbeat_loop(self) -> None:
        cfg = get_config()
        while not self._shutting_down:
            try:
                # Timeout near the beat period, not gcs_rpc_timeout_s: if
                # the GCS received the beat but the ack is lost (one-way
                # partition), a 30s stall here would miss enough beats to
                # get this node declared dead even though its beats arrive.
                reply = await self._gcs.call(
                    "heartbeat",
                    node_id=self.node_id.binary(),
                    resources_available=dict(self.resources_available),
                    demand=self._demand_snapshot(),
                    version=self._resource_version,
                    timeout=max(2 * cfg.heartbeat_interval_s, 2.0),
                )
                if not reply.get("ok") and reply.get("reregister"):
                    # GCS declared us dead (transient stall past the failure
                    # threshold) or restarted without our record: rejoin.
                    logger.warning("GCS lost this node; re-registering")
                    await self._gcs.call(
                        "register_node",
                        node_id=self.node_id.binary(),
                        address=(self.server.host, self.server.port),
                        resources=self.resources_total,
                        object_store_path=self.store_path,
                        labels=self.labels,
                    )
            except Exception as e:
                logger.warning("heartbeat failed: %r", e)
            await asyncio.sleep(cfg.heartbeat_interval_s)

    def _memory_usage(self) -> float:
        cfg = get_config()
        if cfg.testing_memory_usage >= 0:
            return cfg.testing_memory_usage
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, _, v = line.partition(":")
                    info[k] = int(v.split()[0])
            return 1.0 - info["MemAvailable"] / info["MemTotal"]
        except Exception:
            return 0.0

    async def _memory_monitor_loop(self) -> None:
        """OOM protection (reference: memory_monitor.h polling + the
        retriable-LIFO worker killing policy, worker_killing_policy.h:69):
        above the usage threshold, kill the most recently leased task
        worker — its task retries elsewhere/later; actors are spared first
        (their state is harder to recover)."""
        from ray_tpu.core.oom_policies import get_policy

        cfg = get_config()
        if cfg.memory_usage_threshold <= 0:
            return
        policy = get_policy(cfg.oom_killer_policy)
        while not self._shutting_down:
            await asyncio.sleep(cfg.memory_monitor_interval_s)
            usage = self._memory_usage()
            if usage < cfg.memory_usage_threshold:
                continue
            leased = [w for w in self.workers.values()
                      if w.leased and w.proc.poll() is None]
            if not leased:
                continue
            victim = policy.select(leased)
            if victim is None:
                continue
            logger.warning(
                "memory pressure %.0f%% >= %.0f%%: killing worker %s "
                "(%s policy)", usage * 100,
                cfg.memory_usage_threshold * 100,
                victim.worker_id.hex()[:8], policy.name)
            try:
                victim.proc.kill()
            except Exception:
                pass
            # Let the reap loop handle resource return + death report.
            await asyncio.sleep(1.0)

    async def _reap_loop(self) -> None:
        """Detect dead workers; release their resources; tell GCS (reference:
        NodeManager worker-failure handling + plasma client disconnect)."""
        cfg = get_config()
        idle_ttl = 60.0
        while not self._shutting_down:
            await asyncio.sleep(0.2)
            # Expire transfer-cache entries even when no further fetch ever
            # arrives — a finished chunked pull must not pin a materialized
            # multi-GB spilled object for the nodelet's lifetime.
            if self._transfer_cache:
                now = time.monotonic()
                for k in [k for k, (_, ts) in self._transfer_cache.items()
                          if now - ts > 30.0]:
                    self._transfer_cache.pop(k, None)
            for wid, w in list(self.workers.items()):
                code = w.proc.poll()
                if code is not None:
                    del self.workers[wid]
                    if w.resources is not None:
                        pool = self._bundle_pool(getattr(w, "pg_bundle", None))
                        if pool is not None:
                            w.resources.add_to(pool)
                    if w.tpu_chips:
                        self._tpu_chips_free.extend(w.tpu_chips)
                        w.tpu_chips = []
                    self._wake_lease_waiters()
                    if w.leased:
                        try:
                            await self._gcs.call(
                                "report_worker_death",
                                node_id=self.node_id.binary(),
                                worker_address=w.address,
                                reason=f"exit code {code}",
                            )
                        except Exception:
                            pass
                elif (not w.leased and w.ready.is_set()
                      and time.monotonic() - w.last_idle > idle_ttl):
                    # Trim warm pool beyond the configured size.
                    idle = [x for x in self.workers.values()
                            if not x.leased and x.env_key == w.env_key]
                    if len(idle) > cfg.idle_worker_pool_size:
                        w.proc.terminate()
            self.store.reclaim_stale(120)


def main() -> None:  # pragma: no cover - exercised via subprocess
    import argparse
    import json

    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs-host", required=True)
    parser.add_argument("--gcs-port", type=int, required=True)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--resources", default="")
    parser.add_argument("--object-store-memory", type=int, default=0)
    parser.add_argument("--node-name", default="")
    parser.add_argument("--labels", default="")
    args = parser.parse_args()

    resources = json.loads(args.resources) if args.resources else None

    async def _run():
        import signal

        nodelet = Nodelet(
            (args.gcs_host, args.gcs_port),
            args.session_dir,
            host=args.host,
            port=args.port,
            resources=resources,
            object_store_memory=args.object_store_memory or None,
            node_name=args.node_name,
            labels=json.loads(args.labels) if args.labels else None,
        )
        await nodelet.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        try:
            # Reap workers before exiting — otherwise they leak past the
            # session. Bounded: a hung teardown must not outlive the
            # driver's kill grace period with the arena still on disk.
            await asyncio.wait_for(nodelet.stop(), 8)
        except Exception:
            pass
        finally:
            for p in (nodelet.store_path, nodelet.store_path + ".pid"):
                try:
                    os.unlink(p)
                except OSError:
                    pass

    asyncio.run(_run())


if __name__ == "__main__":
    main()
