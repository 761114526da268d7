"""The per-process worker runtime — counterpart of src/ray/core_worker/
(CoreWorker, core_worker.h:166) plus the Cython bridge (_raylet.pyx §2.2).

One Worker instance per process (driver or executor). It owns:
- an EventLoopThread hosting this process's RpcServer (direct worker↔worker
  task pushes and owner↔borrower object resolution),
- the owner memory store (small objects) + shm store client (large objects),
- the submission side: TaskManager (retries/lineage), lease pools keyed by
  SchedulingKey (reference: normal_task_submitter.h:44-58), actor submitters
  with per-handle ordering,
- the execution side: task/actor execution on executor threads, async-actor
  coroutines on the event loop (reference: transport/fiber.h → here plain
  asyncio).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import os
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import backoff as backoff_mod
from ray_tpu._private import flight_recorder as _fr
from ray_tpu._private import serialization as ser
from ray_tpu._private.function_manager import FunctionManager
from ray_tpu._private.ids import (
    ActorID,
    JobID,
    NodeID,
    ObjectID,
    TaskID,
    WorkerID,
)
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.reference_counter import ReferenceCounter
from ray_tpu._private.rpc import (
    ConnectionLost,
    EventLoopThread,
    RemoteError,
    RpcClient,
    RpcServer,
)
from ray_tpu._private.task_manager import TaskManager
from ray_tpu._private.task_spec import (
    DefaultStrategy,
    NodeAffinityStrategy,
    PlacementGroupStrategy,
    ResourceSet,
    SpreadStrategy,
    TaskSpec,
    TaskType,
)
from ray_tpu.core.object_store import MemoryStore, SharedMemoryStore
from ray_tpu.exceptions import (
    ActorDiedError,
    ObjectStoreFullError,
    ActorUnavailableError,
    GetTimeoutError,
    ObjectLostError,
    RayTaskError,
    TaskCancelledError,
    WorkerCrashedError,
)
from ray_tpu.util import metrics as um
from ray_tpu.utils.config import get_config
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Task-event buffer cap (reference: task_event_buffer.h's bounded buffer):
# on sustained GCS unavailability old events are evicted oldest-first and
# counted, instead of growing the requeue list without bound.
_TASK_EVENT_BUFFER_MAX = int(
    os.environ.get("RAY_TPU_TASK_EVENT_BUFFER_MAX", "10000"))


# Runtime metric definitions — one site per metric (the registry dedupes by
# name and silently ignores redefinitions, so inline duplicates would drift).
def _m_tasks_submitted() -> "um.Counter":
    return um.get_counter("ray_tpu_tasks_submitted_total",
                          "Tasks submitted from this process")


def _m_tasks_finished() -> "um.Counter":
    return um.get_counter("ray_tpu_tasks_finished_total",
                          "Tasks executed to completion on this node",
                          tag_keys=("node", "name"))


def _m_tasks_failed() -> "um.Counter":
    return um.get_counter("ray_tpu_tasks_failed_total",
                          "Tasks whose execution raised",
                          tag_keys=("node", "name"))


def _m_task_exec_hist() -> "um.Histogram":
    return um.get_histogram("ray_tpu_task_exec_seconds",
                            "User-code execution latency "
                            "(args ready -> return)", tag_keys=("name",))


def _m_task_e2e_hist() -> "um.Histogram":
    return um.get_histogram("ray_tpu_task_e2e_seconds",
                            "End-to-end task latency observed by the owner "
                            "(submit -> completion)", tag_keys=("name",))


def _m_events_dropped() -> "um.Counter":
    return um.get_counter("ray_tpu_task_events_dropped_total",
                          "Task events evicted from the bounded "
                          "per-process buffer")


def _m_lease_queue_gauge() -> "um.Gauge":
    # Per-process series (pid tag): an idle executor's 0 must not shadow
    # the driver's real backlog in the freshest-wins gauge merge.
    return um.get_gauge("ray_tpu_lease_queue_depth",
                        "Tasks queued in a process's lease pools awaiting "
                        "a worker", tag_keys=("pid",))

_global_worker: Optional["Worker"] = None
_global_lock = threading.Lock()


def global_worker() -> "Worker":
    if _global_worker is None:
        raise RuntimeError(
            "ray_tpu has not been initialized; call ray_tpu.init() first")
    return _global_worker


def global_worker_or_none() -> Optional["Worker"]:
    return _global_worker


def set_global_worker(w: Optional["Worker"]) -> None:
    global _global_worker
    with _global_lock:
        _global_worker = w


# Absent-key sentinel for MemoryStore.pop (a stored None is a real inline
# value — tasks returning None are common and take the fast path).
_MISSING = object()


class ShmMarker:
    """Memory-store placeholder meaning 'value lives in the shm store of
    node_id'."""

    __slots__ = ("node_id",)

    def __init__(self, node_id: bytes):
        self.node_id = node_id


def _enter_trace_context(spec):
    """Make the submitter's span the execution side's current span, so
    spans opened inside the task chain across the hop. Returns a reset
    token (None when the spec carries no context)."""
    if not getattr(spec, "trace_parent", None):
        return None
    from ray_tpu.util import tracing

    return tracing._current_span.set(spec.trace_parent)


def _exit_trace_context(token) -> None:
    if token is None:
        return
    from ray_tpu.util import tracing

    try:
        tracing._current_span.reset(token)
    except ValueError:
        pass  # executor thread changed context (generators): drop


def _current_trace_parent():
    """The submitter's active user span id (None when tracing is idle) —
    captured into every TaskSpec so execution-side spans parent across
    the process hop (reference: tracing_helper.py context injection)."""
    from ray_tpu.util import tracing

    return tracing.current_span_id()


class LeasePool:
    """Leased-worker pool for one SchedulingKey; pipelines queued tasks onto
    leased workers and returns leases when drained (reference:
    NormalTaskSubmitter lease pooling + ReportWorkerBacklog)."""

    def __init__(self, worker: "Worker", sched_key: Tuple,
                 spec_template: TaskSpec,
                 target_node: Optional[bytes] = None):
        self.worker = worker
        self.sched_key = sched_key
        self.resources = dict(spec_template.resources)
        self.runtime_env = spec_template.runtime_env
        self.strategy = spec_template.scheduling_strategy
        # SPREAD pools are per-node: the submitter round-robins tasks across
        # alive nodes at submission time (reference: spread_scheduling_policy
        # assigns the node per task, not per lease).
        self.target_node = target_node
        self.queue: asyncio.Queue = asyncio.Queue()
        self.num_leased = 0
        self.requesting = 0
        self.label_selector = getattr(spec_template, "label_selector", None)
        # Consecutive lease failures: drives the unified full-jitter
        # backoff (reset on any successful grant).
        self.lease_fail_streak = 0

    def maybe_scale_up(self) -> None:
        cfg = get_config()
        # Cap concurrent leases by HOST parallelism, not just queue depth:
        # on a small host, 8-10 worker processes time-slicing the cores
        # thrash (context switches + per-lease shallow push batches) and
        # tiny-task throughput DROPS ~35% vs 4 leases. Multi-core hosts
        # (cpu_count >= max_pending_leases_per_key) are unaffected.
        import os

        host_cap = max(4, os.cpu_count() or 1)
        want = min(self.queue.qsize(), cfg.max_pending_leases_per_key,
                   host_cap)
        while self.num_leased + self.requesting < max(1, want):
            self.requesting += 1
            asyncio.ensure_future(self._acquire_and_pump())

    async def _resolve_target_nodelet(self):
        """Cluster scheduling (reference: two-level scheduling, SURVEY C15):
        pick the nodelet to lease from based on the scheduling strategy.
        Returns (nodelet_client, pg_bundle) or (None, None) when nothing
        fits right now."""
        w = self.worker
        if isinstance(self.strategy, PlacementGroupStrategy):
            pg_bundle = (self.strategy.placement_group_id,
                         max(self.strategy.bundle_index, 0))
            pg = await w.gcs_client.call(
                "get_placement_group", pg_id=self.strategy.placement_group_id)
            if pg is None or pg["state"] != "CREATED":
                return None, None
            node_id = pg["bundle_nodes"].get(pg_bundle[1])
            if node_id is None:
                return None, None
            client = await w.nodelet_client_for_node(node_id)
            return client, pg_bundle
        if isinstance(self.strategy, NodeAffinityStrategy):
            client = await w.nodelet_client_for_node(
                bytes.fromhex(self.strategy.node_id))
            return client, None
        if isinstance(self.strategy, SpreadStrategy):
            if self.target_node is not None:
                try:
                    client = await w.nodelet_client_for_node(self.target_node)
                    return client, None
                except Exception:
                    pass  # assigned node gone — fall through to a GCS pick
            pick = await w.gcs_client.call(
                "pick_node", resources=self.resources, strategy="spread",
                label_selector=self.label_selector)
            if pick is None:
                return None, None
            return await w.nodelet_client_for_node(pick["node_id"]), None
        if self.label_selector:
            # Labels are a cluster property: route through the GCS's
            # composite policy (feasibility incl. label match, then
            # hybrid score) instead of the local-first probe.
            pick = await w.gcs_client.call(
                "pick_node", resources=self.resources,
                label_selector=self.label_selector)
            if pick is None:
                return None, None
            return await w.nodelet_client_for_node(pick["node_id"]), None
        # Default (hybrid): locality first — try the local nodelet without
        # blocking; spill to a GCS-picked node when local is saturated
        # (reference: lease spillback, normal_task_submitter.h:79).
        return w.nodelet_client, None

    async def _lease_once(self):
        """One lease attempt. Returns (lease_reply, nodelet_client)."""
        w = self.worker
        client, pg_bundle = await self._resolve_target_nodelet()
        if client is None:
            return {"ok": False, "error": "no feasible node", "retry": True}, None
        timeout = get_config().worker_start_timeout_s + 5
        if client is w.nodelet_client and not isinstance(
                self.strategy, (PlacementGroupStrategy, NodeAffinityStrategy)):
            # Spillback (reference: ClusterTaskManager spillback + lease
            # retries): probe non-blocking, local node first — the nodelets'
            # own accounting is exact where the GCS heartbeat view is ~1s
            # stale — and keep sweeping until something grants or we time out.
            deadline = time.monotonic() + get_config().worker_start_timeout_s
            backoff = 0.05
            while True:
                lease = await client.call(
                    "lease_worker", owner=list(w.address),
                    resources=self.resources,
                    runtime_env=self.runtime_env, lifetime="task",
                    pg_bundle=pg_bundle, block=False, timeout=timeout)
                if lease.get("ok"):
                    return lease, client
                nodes = await w.gcs_client.call("list_nodes")
                others = [n for n in nodes if n["alive"]
                          and n["node_id"] != w.node_id.binary()]
                if not others:
                    # Single-node cluster: block on the local nodelet (event-
                    # driven wakeup) instead of polling.
                    lease = await client.call(
                        "lease_worker", owner=list(w.address),
                    resources=self.resources,
                        runtime_env=self.runtime_env, lifetime="task",
                        pg_bundle=pg_bundle, block=True, timeout=timeout)
                    return lease, client
                for n in others:
                    remote = await w.nodelet_client_for_node(n["node_id"])
                    lease = await remote.call(
                        "lease_worker", owner=list(w.address),
                    resources=self.resources,
                        runtime_env=self.runtime_env, lifetime="task",
                        pg_bundle=pg_bundle, block=False, timeout=timeout)
                    if lease.get("ok"):
                        return lease, remote
                if time.monotonic() > deadline:
                    return {"ok": False, "error": "lease timeout",
                            "retry": True}, None
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 0.5)
        lease = await client.call(
            "lease_worker", owner=list(w.address),
                    resources=self.resources,
            runtime_env=self.runtime_env, lifetime="task",
            pg_bundle=pg_bundle, block=True, timeout=timeout)
        return lease, client

    async def _acquire_and_pump(self) -> None:
        try:
            lease, nodelet = await self._lease_once()
        except Exception as e:
            logger.warning("lease request failed: %r", e)
            self.requesting -= 1
            # A transient RPC failure must not strand queued tasks: back off
            # (full jitter, so N failed pools don't re-lease in lockstep)
            # and retry the scale-up, same as the resources-busy branch.
            if not self.queue.empty():
                await asyncio.sleep(
                    backoff_mod.delay_for_attempt(self.lease_fail_streak))
                self.lease_fail_streak += 1
                self.maybe_scale_up()
            return
        self.requesting -= 1
        if not lease.get("ok"):
            # Resources busy — tasks stay queued; an existing lease will drain
            # them, or a later submit retries the scale-up.
            if self.num_leased == 0 and not self.queue.empty():
                await asyncio.sleep(backoff_mod.delay_for_attempt(
                    self.lease_fail_streak, initial=0.5, maximum=5.0))
                self.lease_fail_streak += 1
                self.maybe_scale_up()
            return
        self.lease_fail_streak = 0
        self.num_leased += 1
        worker_id = lease["worker_id"]
        addr = tuple(lease["worker_address"])
        client = RpcClient(*addr, name="leased-worker")
        cfg = get_config()
        max_batch = max(1, cfg.task_batch_size)
        window = asyncio.Semaphore(max(1, cfg.task_push_window))
        pending: set = set()
        dead = False
        try:
            while not dead:
                # Fairness: this lease takes ~its share of the queue, so a
                # fast-granted local lease cannot starve spillback/SPREAD
                # leases that are still being acquired (the reference spreads
                # backlog across granted leases the same way).
                active = max(1, self.num_leased + self.requesting)
                qsize = self.queue.qsize()
                limit = max(1, min(max_batch, -(-qsize // active)))
                deep = qsize > active * max_batch
                if not deep and pending:
                    # Shallow queue: no pipelining — finish what's in flight
                    # before taking more, letting other leases claim work.
                    await asyncio.wait(pending,
                                       return_when=asyncio.FIRST_COMPLETED)
                    continue
                batch: List[TaskSpec] = []
                while len(batch) < limit:
                    try:
                        batch.append(self.queue.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                if not batch:
                    if pending:
                        # Let in-flight batches finish; their completion often
                        # unlocks dependents that enqueue more work here.
                        await asyncio.wait(pending,
                                           return_when=asyncio.FIRST_COMPLETED)
                        continue
                    # Lease linger: hold the warm worker briefly — a following
                    # submission wave reuses it without a lease round trip.
                    # NOT under contention: when other submitters were
                    # parked at grant time, an idle hold starves them.
                    if lease.get("contended"):
                        break
                    try:
                        batch.append(await asyncio.wait_for(
                            self.queue.get(), cfg.lease_linger_s))
                    except asyncio.TimeoutError:
                        break
                await window.acquire()
                if dead:
                    for spec in batch:
                        self.queue.put_nowait(spec)
                    window.release()
                    break

                async def one_batch(specs=batch):
                    nonlocal dead
                    try:
                        alive = await self.worker.push_task_batch_to(
                            client, addr, specs)
                        if not alive:
                            dead = True
                    finally:
                        window.release()

                t = asyncio.ensure_future(one_batch())
                pending.add(t)
                t.add_done_callback(pending.discard)
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
        finally:
            self.num_leased -= 1
            await client.close()
            try:
                await nodelet.call("return_worker", worker_id=worker_id)
            except Exception:
                pass
            if not self.queue.empty():
                self.maybe_scale_up()
            self.worker._update_lease_queue_gauge()


class ActorSubmitter:
    """Per-actor ordered submission (reference: actor_task_submitter.h:75).

    A single pump coroutine drains a FIFO queue so request *writes* hit the
    wire in seq_no order; replies are awaited concurrently so an async actor
    still sees pipelined calls.
    """

    def __init__(self, worker: "Worker", actor_id: ActorID):
        self.worker = worker
        self.actor_id = actor_id
        self.client: Optional[RpcClient] = None
        self.control_client: Optional[RpcClient] = None
        self.address: Optional[Tuple[str, int]] = None
        self.queue: asyncio.Queue = asyncio.Queue()
        self._pump_task: Optional[asyncio.Task] = None
        self._held: Optional[tuple] = None

    def enqueue(self, spec: TaskSpec, max_task_retries: int) -> None:
        self.queue.put_nowait((spec, max_task_retries, 0))
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.ensure_future(self._pump())

    MAX_BATCH = 32

    async def _pump(self) -> None:
        # Persistent: parks on queue.get() between calls instead of exiting,
        # so steady-state submission wakes a waiter (~µs) rather than
        # creating a fresh Task per call.
        first = item = batch = fut = spec = deps = None
        while True:
            # Drop the previous iteration's locals BEFORE parking: a parked
            # coroutine frame pins its locals, and a retained TaskSpec pins
            # its arg ObjectRefs — the owner could never free them.
            first = item = batch = fut = spec = deps = None
            if self._held is not None:
                first, self._held = self._held, None
            else:
                first = await self.queue.get()
            # Adaptive batching: drain whatever is queued (up to MAX_BATCH)
            # into one RPC frame — collapses per-call frame/syscall/task
            # overhead for pipelined submitters while a lone call still goes
            # out immediately as a batch of one. Dependency gating stays in
            # FIFO order (sync-actor ordering contract): a task whose owned
            # args are pending flushes the batch ahead of it, then waits.
            # A streaming call (num_returns == -1) goes out alone, in its
            # place in the order: its reply is the end of its stream, and a
            # shared frame replies only after *all* members finish, so every
            # stream of a batch would end for its consumer when the longest
            # one does.
            batch = []
            item: Any = first
            while True:
                streaming = item[0].num_returns == -1
                deps = self.worker.unresolved_owned_deps(item[0])
                if batch and (deps or streaming):
                    self._held = item
                    break
                if deps:
                    await self.worker.wait_owned_deps(deps)
                batch.append(item)
                if streaming or len(batch) >= self.MAX_BATCH:
                    break
                try:
                    item = self.queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
            if not batch:
                continue
            now = time.time()
            for spec, _, _ in batch:
                # Actor tasks skip leasing; stamp dispatch time so the
                # lifecycle breakdown still covers the submitter queue.
                spec.lease_ts = now
            try:
                client = await self._ensure_client()
                # Long-running pinned loops (compiled-DAG channels) must
                # not occupy the fast lane's sequential connection — they
                # reply only at teardown, which would head-of-line block
                # every later call. The same applies to any call in a named
                # concurrency group (e.g. serve's routing long-poll, which
                # parks server-side for its full poll window): a shared
                # push_actor_task_batch frame replies only after *all*
                # members finish, so batching a parked poll with a fast
                # call stalls the fast call for the poll window. Ship both
                # via the control lane, one frame per call.
                pinned = [it for it in batch
                          if it[0].actor_method_name
                          == "__dag_channel_loop__"
                          or it[0].concurrency_group]
                if pinned:
                    batch = [it for it in batch if it not in pinned]
                    ctl = self.control_client or client
                    for spec, retries, attempt in pinned:
                        try:
                            pfut = await ctl.start_call(
                                "push_actor_task", spec=ser_spec(spec))
                        except (ConnectionLost,
                                asyncio.TimeoutError) as e:
                            # Same contract as a failed batch send: retry
                            # or fail the task — never drop it (a dropped
                            # loop leaves the driver blocked on a channel
                            # that no one will ever write).
                            await self._on_send_failure(
                                spec, retries, attempt, e)
                            continue
                        pfut.add_done_callback(
                            lambda f, s=spec, r=retries, a=attempt:
                            self._on_reply_done(s, r, a, f))
                    if not batch:
                        continue
                # Actor specs cross as ser_spec bytes (normal tasks ship
                # TaskSpec objects — one frame pickle, shared memo). Actor
                # frames may sit decoded in long-lived receiver state (fast
                # lane loop vars, channel-loop kwargs); opaque bytes keep
                # arg ObjectRefs/buffers from materializing borrows or
                # pinning receive frames beyond task execution — switching
                # them to objects leaked a device-object borrow in the
                # channel-DAG suite.
                _fr.note_batch("actor", len(batch))
                # Sampled flight-recorder decomposition: ser_spec time folds
                # into the serialize phase; start_call stamps frame/syscall.
                rec = _fr.maybe_begin_call(batch[0][0].function_name)
                if len(batch) == 1:
                    spec, retries, attempt = batch[0]
                    if rec is None:
                        payload = ser_spec(spec)
                    else:
                        t = time.perf_counter_ns()
                        payload = ser_spec(spec)
                        rec["pre_serialize_ns"] = time.perf_counter_ns() - t
                    fut = await client.start_call("push_actor_task",
                                                  fr_rec=rec, spec=payload)
                else:
                    if rec is None:
                        payloads = [ser_spec(s) for s, _, _ in batch]
                    else:
                        t = time.perf_counter_ns()
                        payloads = [ser_spec(s) for s, _, _ in batch]
                        rec["pre_serialize_ns"] = time.perf_counter_ns() - t
                    fut = await client.start_call(
                        "push_actor_task_batch", fr_rec=rec,
                        specs=payloads)
            except (ConnectionLost, asyncio.TimeoutError) as e:
                for spec, retries, attempt in batch:
                    await self._on_send_failure(spec, retries, attempt, e)
                continue
            except (ActorDiedError, ActorUnavailableError) as e:
                for spec, _, _ in batch:
                    self.worker.task_manager.fail_permanently(
                        spec.task_id, ser.serialize_error(e))
                continue
            if len(batch) == 1:
                spec, retries, attempt = batch[0]
                fut.add_done_callback(
                    lambda f, s=spec, r=retries, a=attempt, rc=rec:
                    self._on_reply_done(s, r, a, f, rc))
            else:
                asyncio.ensure_future(
                    self._handle_batch_reply(batch, fut, rec))

    def _on_reply_done(self, spec: TaskSpec, retries: int, attempt: int,
                       fut: "asyncio.Future", rec: Optional[dict] = None
                       ) -> None:
        """Done-callback reply path: the overwhelmingly common reply (ok,
        inline/shm results, no borrows) completes synchronously with no Task
        creation; anything else falls back to the async handler."""
        if fut.cancelled() or fut.exception() is not None:
            asyncio.ensure_future(
                self._handle_reply(spec, retries, attempt, fut))
            return
        reply = fut.result()
        if rec is not None:
            t0 = time.perf_counter_ns()
            handled = self.worker.handle_task_reply_fast(spec, reply)
            _fr.finish_call_from_reply(
                rec, reply, time.perf_counter_ns() - t0)
            if handled:
                return
        elif self.worker.handle_task_reply_fast(spec, reply):
            return
        asyncio.ensure_future(
            self._handle_reply(spec, retries, attempt, fut))

    async def _handle_batch_reply(self, batch, fut: "asyncio.Future",
                                  rec: Optional[dict] = None) -> None:
        try:
            reply = await asyncio.wait_for(fut, 86400.0)
        except (ConnectionLost, RemoteError, asyncio.TimeoutError) as e:
            for spec, retries, attempt in batch:
                await self._on_send_failure(spec, retries, attempt, e)
            if self._pump_task is None or self._pump_task.done():
                self._pump_task = asyncio.ensure_future(self._pump())
            return
        t0 = time.perf_counter_ns() if rec is not None else 0
        for (spec, _, _), item in zip(batch, reply["replies"]):
            await self.worker.handle_task_reply(spec, item)
        if rec is not None:
            _fr.finish_call_from_reply(
                rec, reply, time.perf_counter_ns() - t0)

    async def _on_send_failure(self, spec: TaskSpec, retries: int,
                               attempt: int, exc: BaseException) -> None:
        self.reset()
        if attempt < retries:
            # Unified policy: grow with the attempt number and jitter —
            # a fixed initial sleep made every resubmitting caller hammer
            # a restarting actor in lockstep under delay chaos.
            await asyncio.sleep(backoff_mod.delay_for_attempt(attempt))
            self.queue.put_nowait((spec, retries, attempt + 1))
            return
        # Distinguish dead vs transient for the error type.
        try:
            info = await self.worker.gcs_client.call(
                "get_actor", actor_id=self.actor_id.binary())
        except Exception:
            info = None
        if info is not None and info["state"] == "DEAD":
            err: BaseException = ActorDiedError(
                f"actor {self.actor_id} died: {info['death_cause']}")
        else:
            err = ActorUnavailableError(
                f"actor {self.actor_id} unreachable: {exc!r}")
        self.worker.task_manager.fail_permanently(
            spec.task_id, ser.serialize_error(err))

    async def _handle_reply(self, spec: TaskSpec, retries: int, attempt: int,
                            fut: "asyncio.Future") -> None:
        try:
            reply = await asyncio.wait_for(fut, 86400.0)
        except (ConnectionLost, RemoteError, asyncio.TimeoutError) as e:
            await self._on_send_failure(spec, retries, attempt, e)
            if self._pump_task is None or self._pump_task.done():
                self._pump_task = asyncio.ensure_future(self._pump())
            return
        await self.worker.handle_task_reply(spec, reply)

    async def _ensure_client(self) -> RpcClient:
        if self.client is not None:
            return self.client
        cfg = get_config()
        deadline = time.monotonic() + cfg.worker_start_timeout_s
        # Event-driven: the worker's GCS pubsub subscription pushes actor
        # state transitions; we wait on those instead of 50ms polling
        # (reference: actor submitters subscribe to GCS actor pubsub).
        w = self.worker
        info = await w.actor_state(self.actor_id, refresh=True)
        rechecked = False
        while True:
            if info is None:
                # Registration race: anonymous creation is fire-and-forget,
                # so this process's register_actor RPC may still be in
                # flight (delayed/retrying) when the first task's get_actor
                # lands. None is PENDING while that send is outstanding —
                # raising "was never created" here failed the first call
                # spuriously under delay chaos.
                cached = w._actor_states.get(self.actor_id.hex())
                if cached is not None:
                    # e.g. the poisoned DEAD entry a failed async
                    # registration writes locally.
                    info = cached
                    continue
                if self.actor_id.hex() in w._registering_actors:
                    if time.monotonic() > deadline:
                        raise ActorUnavailableError(
                            f"actor {self.actor_id} registration still in "
                            f"flight after worker_start_timeout_s")
                    info = await w.actor_state(
                        self.actor_id,
                        wait_change=min(1.0, max(
                            0.05, deadline - time.monotonic())))
                    continue
                if not rechecked:
                    # The registration may have completed between our
                    # get_actor and the in-flight check: read once more
                    # AFTER observing the set empty before condemning.
                    rechecked = True
                    info = await w.actor_state(self.actor_id, refresh=True)
                    continue
                raise ActorDiedError(f"actor {self.actor_id} was never created")
            if info["state"] == "ALIVE" and info.get("address"):
                self.address = tuple(info["address"])
                self.client = RpcClient(*self.address, name="actor")
                # Prefer the worker's fast lane (zero intra-worker hops;
                # see Worker._start_fast_lane) when the actor runs one —
                # same frame protocol, different port. The control client
                # stays around for cancel/generator RPCs.
                try:
                    fl = await self.client.call("fast_lane_info", timeout=5)
                    if fl and fl.get("port"):
                        self.control_client = self.client
                        self.client = RpcClient(
                            self.address[0], fl["port"], name="actor-fl")
                except Exception:
                    pass  # older/busy worker: normal lane works fine
                return self.client
            if info["state"] == "DEAD":
                # A poisoned local cache entry (failed async registration)
                # carries "error", a GCS view carries "death_cause".
                raise ActorDiedError(
                    f"actor {self.actor_id} is dead: "
                    f"{info.get('death_cause') or info.get('error')}")
            if time.monotonic() > deadline:
                raise ActorUnavailableError(
                    f"actor {self.actor_id} stuck in {info['state']}")
            info = await w.actor_state(
                self.actor_id,
                wait_change=min(5.0, deadline - time.monotonic()))

    def reset(self) -> None:
        client, self.client, self.address = self.client, None, None
        control = getattr(self, "control_client", None)
        self.control_client = None
        for c in (client, control):
            if c is not None:
                asyncio.ensure_future(c.close())


def _prepare_runtime_env(runtime_env, gcs_call):
    if not runtime_env:
        return runtime_env
    from ray_tpu._private import runtime_env as rt_env

    return rt_env.prepare(runtime_env, gcs_call)


def ser_spec(spec: TaskSpec) -> bytes:
    import pickle

    return pickle.dumps(spec, protocol=5)


def deser_spec(data: bytes) -> TaskSpec:
    import pickle

    return pickle.loads(data)


class Worker:
    def __init__(
        self,
        mode: str,  # "driver" | "worker"
        gcs_address: Tuple[str, int],
        nodelet_address: Tuple[str, int],
        store_path: str,
        session_dir: str,
        job_id: Optional[JobID] = None,
        node_id: Optional[NodeID] = None,
        worker_id: Optional[WorkerID] = None,
    ):
        self.mode = mode
        self.worker_id = worker_id or WorkerID.from_random()
        self.node_id = node_id or NodeID.nil()
        self.session_dir = session_dir
        self.loop_thread = EventLoopThread(f"ray_tpu_{mode}_io")
        self.loop = self.loop_thread.loop
        self.memory_store = MemoryStore(self.loop)
        self.shm = SharedMemoryStore(store_path)
        # Spill-before-evict: the arena must not silently drop objects under
        # pressure — put_shm_or_spill moves the LRU victim to disk first.
        self.shm.set_auto_evict(False)
        self.ref_counter = ReferenceCounter(on_zero=self._on_owned_ref_zero)
        # True once the node's spill dir has been observed to exist —
        # gates the per-ref spill unlink (see _on_owned_ref_zero).
        self._spill_dir_seen = False
        self.task_manager = TaskManager(self._store_task_result)
        self.server = RpcServer()
        self.address: Optional[Tuple[str, int]] = None
        self.gcs_address = gcs_address
        self.nodelet_address = nodelet_address
        self.gcs_client: Optional[RpcClient] = None
        self.nodelet_client: Optional[RpcClient] = None
        self.job_id = job_id or JobID.from_int(0)
        self.function_manager = FunctionManager(self._gcs_call_sync)
        self._put_counter = 0
        self._put_lock = threading.Lock()
        self._task_counter_lock = threading.Lock()
        self._lease_pools: Dict[Tuple, LeasePool] = {}
        self._submit_buf: List[TaskSpec] = []
        self._submit_buf_lock = threading.Lock()
        self._spread_nodes: List[bytes] = []
        self._spread_rr = 0
        self._spread_refresh_started = False
        self._actor_submitters: Dict[ActorID, ActorSubmitter] = {}
        self._actor_seq_nos: Dict[ActorID, int] = {}
        # Remote nodelet clients for cluster-wide leasing, keyed by node id.
        self._nodelet_clients: Dict[bytes, RpcClient] = {}
        # Execution side.
        self._task_executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, get_config().task_executor_threads),
            thread_name_prefix="task_exec")
        self._actor_instance: Any = None
        self._actor_creation_spec: Optional[TaskSpec] = None
        self._actor_executors: Dict[str, concurrent.futures.ThreadPoolExecutor] = {}
        self._actor_is_async = False
        self._running_tasks: Dict[TaskID, Any] = {}
        self._cancelled_tasks: set = set()
        # Streaming generators (owner side): task_id -> GeneratorState.
        self._generators: Dict[TaskID, Any] = {}
        # In-flight lineage recoveries: object_id -> future.
        self._recoveries: Dict[ObjectID, "asyncio.Future"] = {}
        # Partial chunked pulls this process can peer-serve:
        # object binary id -> (flat buffer, set of landed chunk offsets).
        self._active_pulls: Dict[bytes, Tuple[bytearray, set]] = {}
        self._peer_chunk_clients: Dict[Tuple[str, int], RpcClient] = {}
        # Actor-state cache fed by GCS pubsub (replaces per-submitter
        # polling). Keyed by actor_id hex; _actor_pulse fires on any update.
        self._actor_states: Dict[str, Dict[str, Any]] = {}
        self._actor_pulse = asyncio.Event()
        self._actor_sub_started = False
        # Anonymous-actor registrations this process fired asynchronously
        # and whose GCS reply hasn't landed: while an id is in here,
        # get_actor -> None means PENDING, not "was never created".
        self._registering_actors: set = set()
        self._log_sub_started = False
        # Where each GCS pub/sub subscription of this process stands
        # (channel -> last sequence taken). For observation only:
        # `_subscribe` writes it and holds its own cursor; a test reads it.
        self._pubsub_cursors: Dict[str, int] = {}
        # Task-event buffer (timeline/profiling floor).
        self._task_events: List[Dict[str, Any]] = []
        self._task_events_lock = threading.Lock()
        self._task_events_flusher_started = False
        # Executor side: cached clients for streaming items back to owners.
        self._gen_clients: Dict[Tuple[str, int], RpcClient] = {}
        self.connected = False
        self._shutdown = False
        # The task currently executing in this process (execution context).
        self._current_task_id: Optional[TaskID] = None
        # Device-object plane (experimental/device_objects.py): HBM-resident
        # tensors this process holds, and src addresses of device objects this
        # process owns (for the owner-driven free protocol).
        self._device_object_store: Any = None
        self.device_object_srcs: Dict[bytes, Tuple[str, int]] = {}

    @property
    def device_object_store(self):
        if self._device_object_store is None:
            from ray_tpu.experimental.device_objects import DeviceObjectStore

            self._device_object_store = DeviceObjectStore()
        return self._device_object_store

    def _maybe_device(self, value: Any) -> Any:
        """Materialize device-object skeletons on the local device (no-op for
        everything else). Must run OFF the event loop."""
        if type(value).__name__ == "DeviceObjectValue":
            from ray_tpu.experimental import device_objects as devobj

            if isinstance(value, devobj.DeviceObjectValue):
                return devobj.resolve_sync(self, value)
        return value

    async def _maybe_device_async(self, value: Any) -> Any:
        if type(value).__name__ == "DeviceObjectValue":
            from ray_tpu.experimental import device_objects as devobj

            if isinstance(value, devobj.DeviceObjectValue):
                return await devobj.resolve_async(self, value)
        return value

    # ------------------------------------------------------------------
    # Bootstrap
    # ------------------------------------------------------------------
    def connect(self) -> None:
        async def _setup():
            self.address = await self.server.start()
            self._register_handlers()
            self.gcs_client = RpcClient(*self.gcs_address, name="gcs")
            self.nodelet_client = RpcClient(*self.nodelet_address, name="nodelet")
            await self.gcs_client.connect()
            await self.nodelet_client.connect()
            asyncio.ensure_future(self._borrow_report_loop())
            asyncio.ensure_future(self._borrower_audit_loop())
            # Prime the spread-RR node cache so the first SPREAD wave
            # already distributes (the refresh loop keeps it fresh).
            try:
                nodes = await self.gcs_client.call("list_nodes")
                self._spread_nodes = [n["node_id"] for n in nodes
                                      if n["alive"]]
            except Exception:
                pass

        self.loop_thread.run(_setup())
        self.connected = True
        set_global_worker(self)
        self._preregister_metrics()

    def _preregister_metrics(self) -> None:
        """Create this process's runtime metrics up front (Prometheus
        practice: series should exist at zero before first activity, so
        dashboards and the live metrics-contract test see every promised
        name as soon as the process joins the cluster)."""
        _m_tasks_submitted()
        _m_tasks_finished()
        _m_tasks_failed()
        _m_events_dropped().inc(0)
        _m_task_exec_hist()
        _m_task_e2e_hist()
        _m_lease_queue_gauge().set(0.0, tags={"pid": str(os.getpid())})

    async def nodelet_client_for_node(self, node_id: bytes) -> RpcClient:
        """Cached RPC client to any node's nodelet (for spillback / PG /
        node-affinity leases). The local nodelet reuses the primary client."""
        if self.node_id is not None and node_id == self.node_id.binary():
            return self.nodelet_client
        client = self._nodelet_clients.get(node_id)
        if client is not None:
            return client
        nodes = await self.gcs_client.call("list_nodes")
        info = next((n for n in nodes if n["node_id"] == node_id), None)
        if info is None:
            raise ObjectLostError(f"node {node_id.hex()[:12]} not in cluster")
        client = RpcClient(*info["address"], name="nodelet-remote")
        self._nodelet_clients[node_id] = client
        return client

    def disconnect(self) -> None:
        if not self.connected:
            return
        self._shutdown = True

        async def _teardown():
            try:
                # Graceful exit releases our borrows immediately instead of
                # waiting for the owner's audit to notice we're gone.
                await asyncio.wait_for(self._flush_borrow_reports(), 2)
            except Exception:
                pass
            if self.gcs_client:
                await self.gcs_client.close()
            if self.nodelet_client:
                await self.nodelet_client.close()
            for c in self._nodelet_clients.values():
                await c.close()
            await self.server.stop()

        try:
            self.loop_thread.run(_teardown(), timeout=5)
        except Exception:
            pass
        self.connected = False
        set_global_worker(None)
        self._task_executor.shutdown(wait=False)
        self.loop_thread.stop()

    def _register_handlers(self) -> None:
        s = self.server
        s.register("push_task", self._rpc_push_task)
        s.register("push_task_batch", self._rpc_push_task_batch)
        s.register("report_generator_item", self._rpc_report_generator_item)
        s.register("create_actor", self._rpc_create_actor)
        s.register("push_actor_task", self._rpc_push_actor_task)
        s.register("push_actor_task_batch", self._rpc_push_actor_task_batch)
        s.register("get_object", self._rpc_get_object)
        s.register("peer_fetch_chunk", self._rpc_peer_fetch_chunk)
        s.register("wait_object", self._rpc_wait_object)
        s.register("update_borrows", self._rpc_update_borrows)
        s.register("check_borrows", self._rpc_check_borrows)
        s.register("free_objects", self._rpc_free_objects)
        s.register("cancel_task", self._rpc_cancel_task)
        s.register("exit_worker", self._rpc_exit_worker)
        s.register("ping", self._rpc_ping)
        s.register("fast_lane_info", self._rpc_fast_lane_info)
        s.register("dag_method_info", self._rpc_dag_method_info)
        s.register("dump_stacks", self._rpc_dump_stacks)
        s.register("cpu_profile", self._rpc_cpu_profile)
        s.register("heap_profile", self._rpc_heap_profile)
        s.register("overhead_breakdown", self._rpc_overhead_breakdown)
        s.register("flight_record", self._rpc_flight_record)
        s.register("device_object_fetch", self._rpc_device_object_fetch)
        s.register("device_object_fetch_shm", self._rpc_device_object_fetch_shm)
        s.register("device_object_mesh_send", self._rpc_device_object_mesh_send)
        s.register("device_object_free", self._rpc_device_object_free)
        s.register("dag_channel_push", self._rpc_dag_channel_push)
        s.register("dag_channel_close", self._rpc_dag_channel_close)
        s.register("dag_channel_destroy", self._rpc_dag_channel_destroy)
        s.register("dag_channel_close_shm", self._rpc_dag_channel_close_shm)

    async def _rpc_dump_stacks(self) -> Dict[str, Any]:
        """All-thread python stacks of this worker (reference: the
        dashboard agent's py-spy stack-dump endpoint,
        dashboard/modules/reporter/ — here native sys._current_frames,
        which needs no ptrace and works on any worker)."""
        import traceback

        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        stacks = {}
        for ident, frame in frames.items():
            label = f"{names.get(ident, '?')} ({ident})"
            stacks[label] = "".join(traceback.format_stack(frame))
        return {"pid": os.getpid(), "stacks": stacks}

    async def _rpc_cpu_profile(self, duration: float = 5.0,
                               hz: float = 99.0) -> Dict[str, Any]:
        """Sampling CPU profile of this worker → folded stacks (reference:
        the reporter agent's py-spy record/flamegraph endpoint; see
        _private/profiler.py for why sampling is in-process here). Runs on
        a dedicated thread so task-executor threads keep executing — they
        are exactly what the caller wants to observe."""
        from ray_tpu._private import profiler

        return await asyncio.get_running_loop().run_in_executor(
            None, profiler.sample_folded, duration, hz)

    async def _rpc_heap_profile(self, duration: float = 3.0,
                                top: int = 50) -> Dict[str, Any]:
        """tracemalloc allocation profile (reference: the reporter agent's
        memray attach endpoint)."""
        from ray_tpu._private import profiler

        return await asyncio.get_running_loop().run_in_executor(
            None, profiler.heap_snapshot, duration, top)

    async def _rpc_overhead_breakdown(self) -> Dict[str, Any]:
        """Sampled per-call overhead decomposition of calls THIS process
        issued (workers are submitters too: actor-to-actor calls, borrowed
        refs) — fanned cluster-wide by the nodelet."""
        return _fr.overhead_breakdown()

    async def _rpc_flight_record(self) -> Dict[str, Any]:
        """Flight-recorder ring dump + wire/loop summaries for this
        process."""
        return _fr.flight_snapshot()

    async def _rpc_dag_channel_push(self, key: str, payload) -> Dict[str, Any]:
        from ray_tpu.experimental.channel import rpc_channel

        return await rpc_channel.rpc_push(self, key, payload)

    async def _rpc_dag_channel_close(self, key: str) -> Dict[str, Any]:
        from ray_tpu.experimental.channel import rpc_channel

        return await rpc_channel.rpc_close(self, key)

    async def _rpc_dag_channel_destroy(self, key: str) -> Dict[str, Any]:
        from ray_tpu.experimental.channel import rpc_channel

        return await rpc_channel.rpc_destroy(self, key)

    async def _rpc_dag_channel_close_shm(self, path: str) -> Dict[str, Any]:
        from ray_tpu.experimental.channel import rpc_channel

        return await rpc_channel.rpc_close_shm(self, path)

    async def _rpc_device_object_fetch(self, object_id: bytes) -> Dict[str, Any]:
        from ray_tpu.experimental import device_objects as devobj

        return await devobj.rpc_fetch(self, object_id)

    async def _rpc_device_object_fetch_shm(
            self, object_id: bytes) -> Dict[str, Any]:
        from ray_tpu.experimental import device_objects as devobj

        return await devobj.rpc_fetch_shm(self, object_id)

    async def _rpc_device_object_mesh_send(
            self, object_id: bytes,
            dst_ids: List[List[int]]) -> Dict[str, Any]:
        from ray_tpu.experimental import device_objects as devobj

        return await devobj.rpc_mesh_send(self, object_id, dst_ids)

    async def _rpc_device_object_free(self, object_id: bytes) -> Dict[str, Any]:
        from ray_tpu.experimental import device_objects as devobj

        return await devobj.rpc_free(self, object_id)

    def _gcs_call_sync(self, method: str, **kwargs) -> Any:
        return self.loop_thread.run(
            self.gcs_client.call_retrying(method, **kwargs))

    # ------------------------------------------------------------------
    # Owned-object lifecycle
    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # Task events / timeline (reference: task_event_buffer.h ->
    # GcsTaskManager -> `ray timeline` chrome trace)
    # ------------------------------------------------------------------
    def record_event(self, event: Dict[str, Any]) -> None:
        """Append one event to the task-event buffer and make sure the
        flusher runs. Used by task execution AND user tracing spans
        (util/tracing.py) — the single entry point to the pipeline.
        The buffer is bounded: oldest events are dropped (and counted)
        rather than growing without limit while the GCS is unreachable."""
        event.setdefault("pid", os.getpid())
        event.setdefault("node_id", self.node_id.hex())
        dropped = 0
        with self._task_events_lock:
            self._task_events.append(event)
            overflow = len(self._task_events) - _TASK_EVENT_BUFFER_MAX
            if overflow > 0:
                del self._task_events[:overflow]
                dropped = overflow
            if not self._task_events_flusher_started:
                self._task_events_flusher_started = True
                self.loop.call_soon_threadsafe(
                    lambda: asyncio.ensure_future(self._task_event_loop()))
        if dropped:
            self._count_dropped_events(dropped)

    def _observe_task_done(self, spec: TaskSpec) -> None:
        """Owner-side end-to-end latency (submit -> result landed)."""
        if not spec.submitted_ts:
            return
        _m_task_e2e_hist().observe(time.time() - spec.submitted_ts,
                                   tags={"name": spec.function_name})

    @staticmethod
    def _count_dropped_events(n: int) -> None:
        _m_events_dropped().inc(n)

    def record_task_event(self, spec: TaskSpec, start_ts: float,
                          end_ts: float, ok: bool,
                          args_ready_ts: Optional[float] = None) -> None:
        event = {
            "task_id": spec.task_id.hex(),
            "name": spec.function_name,
            "type": spec.task_type.name,
            "start_ts": start_ts,
            "end_ts": end_ts,
            "ok": ok,
        }
        # Lifecycle breakdown (SUBMITTED → LEASE_GRANTED → ARGS_READY →
        # RUNNING → FINISHED): owner-side stamps ride the spec, execution
        # stamps are ours. state.task_latency_breakdown() aggregates these.
        if spec.submitted_ts:
            event["submitted_ts"] = spec.submitted_ts
        if spec.lease_ts:
            event["lease_ts"] = spec.lease_ts
        if args_ready_ts:
            event["args_ready_ts"] = args_ready_ts
        if spec.trace_parent:
            event["parent"] = spec.trace_parent
        self.record_event(event)
        # Same "node" vocabulary as the nodelet's metrics (node_name, which
        # defaults to the id prefix): PromQL joins/group-bys across metric
        # families must match. Executors carry it in their spawn env.
        node = (os.environ.get("RAY_TPU_NODE_NAME")
                or self.node_id.hex()[:8])
        counter = _m_tasks_finished() if ok else _m_tasks_failed()
        counter.inc(tags={"node": node, "name": spec.function_name})
        if args_ready_ts is not None:
            # Only when user code actually ran: a failed arg fetch has no
            # exec phase, and charging fetch time here would corrupt the
            # exec-latency panel.
            _m_task_exec_hist().observe(end_ts - args_ready_ts,
                                        tags={"name": spec.function_name})
        if spec.trace_parent:
            # Stitched traces: runtime phases as spans chained under this
            # task's row (which itself parents to the driver-side span).
            from ray_tpu.util import tracing

            tracing.emit_runtime_spans(self, spec, start_ts, args_ready_ts,
                                       end_ts)

    async def _task_event_loop(self) -> None:
        while not self._shutdown:
            await asyncio.sleep(1.0)
            with self._task_events_lock:
                events, self._task_events = self._task_events, []
            if not events:
                continue
            t0 = time.monotonic()
            try:
                await self.gcs_client.call("report_task_events",
                                           events=events)
            except Exception:
                dropped = 0
                with self._task_events_lock:
                    requeued = events + self._task_events
                    overflow = len(requeued) - _TASK_EVENT_BUFFER_MAX
                    if overflow > 0:
                        requeued = requeued[overflow:]
                        dropped = overflow
                    self._task_events = requeued
                if dropped:
                    self._count_dropped_events(dropped)
            else:
                um.telemetry_flush_histogram().observe(
                    time.monotonic() - t0, tags={"pipeline": "task_events"})

    @property
    def spill_dir(self) -> str:
        return os.path.join(self.session_dir, "spill", self.node_id.hex())

    def put_shm_or_spill(self, object_id: ObjectID,
                         obj: ser.SerializedObject) -> None:
        """Store in shm; on arena pressure, spill LRU victims to the node's
        spill dir until the new object fits (reference:
        local_object_manager.h — spill-before-evict so nothing is silently
        dropped; readers fall back to the spill files transparently)."""
        from ray_tpu.core.object_store import spill_write

        try:
            self.shm.put_serialized(object_id, obj)
            return
        except ObjectStoreFullError:
            pass
        last_victim = None
        while True:
            victim = self.shm.lru_candidate()
            if victim is None or victim == last_victim:
                break
            last_victim = victim
            vobj = self.shm.get_serialized(victim)
            if vobj is not None:
                spill_write(self.spill_dir, victim, vobj)
                del vobj  # drop the read pin before deleting
            logger.info("shm pressure: spilled %s to disk", victim)
            self.shm.delete(victim)
            try:
                self.shm.put_serialized(object_id, obj)
                return
            except ObjectStoreFullError:
                continue
        # Nothing evictable (or object larger than the arena): spill the
        # new object itself.
        logger.warning("shm full; spilling %s (%d bytes) to disk",
                       object_id, obj.total_bytes())
        spill_write(self.spill_dir, object_id, obj)

    def read_spilled(self, object_id: ObjectID
                     ) -> Optional[ser.SerializedObject]:
        from ray_tpu.core.object_store import spill_read

        return spill_read(self.spill_dir, object_id)

    def _on_owned_ref_zero(self, object_id: ObjectID) -> None:
        if self._device_object_store is not None or self.device_object_srcs:
            from ray_tpu.experimental import device_objects as devobj

            devobj.on_owner_ref_zero(self, object_id)
        val = self.memory_store.pop(object_id, _MISSING)
        self.task_manager.drop_lineage(object_id)
        if val is not _MISSING and not isinstance(val, ShmMarker):
            # Inline value: it never touched the arena and inline objects
            # are never spilled — done. (Small task returns dominate ref
            # churn; the arena probe + spill unlink are syscalls.)
            del val
            return
        try:
            self.shm.delete(object_id)
        except Exception:
            pass
        # No spill dir on this node → nothing was ever spilled here; skip
        # the unlink + path-join. The existence check is a fresh stat
        # every time (a timed negative cache would let an object spilled
        # and freed inside the window leak its file); once the dir
        # exists, that fact is cached forever — dirs are never removed
        # within a session.
        if not self._spill_dir_seen:
            if not os.path.isdir(self.spill_dir):
                return
            self._spill_dir_seen = True
        from ray_tpu.core.object_store import spill_delete

        spill_delete(self.spill_dir, object_id)

    def _store_task_result(self, object_id: ObjectID, result: Any) -> None:
        """TaskManager completion callback: result is SerializedObject or
        ShmMarker."""
        self.memory_store.put(object_id, result)

    # ------------------------------------------------------------------
    # Public API: put / get / wait
    # ------------------------------------------------------------------
    def allocate_put_id(self) -> ObjectID:
        with self._put_lock:
            self._put_counter += 1
            idx = self._put_counter
        return ObjectID.for_put(TaskID.for_task(self.job_id), idx)

    def put(self, value: Any) -> ObjectRef:
        return self.put_with_id(self.allocate_put_id(), value)

    def put_with_id(self, object_id: ObjectID, value: Any) -> ObjectRef:
        obj = ser.serialize(value)
        cfg = get_config()
        if obj.total_bytes() > cfg.max_inline_object_size:
            self.put_shm_or_spill(object_id, obj)
            self.memory_store.put(object_id, ShmMarker(self.node_id.binary()))
        else:
            self.memory_store.put(object_id, obj)
        ref = ObjectRef(object_id, owner_address=self.address)
        self.ref_counter.add_owned_ref(object_id)
        return ref

    def get(self, refs: List[ObjectRef], timeout: Optional[float] = None) -> List[Any]:
        # Fast path: every ref already resolved locally (memory store value or
        # local shm) — deserialize on the calling thread, no loop round trip.
        objs = []
        for ref in refs:
            entry = self.memory_store.get_if_exists(ref.id)
            if isinstance(entry, ser.SerializedObject):
                objs.append(entry)
                continue
            obj = self.shm.get_serialized(ref.id)
            if obj is None:
                break
            objs.append(obj)
        if len(objs) == len(refs):
            out = []
            for obj in objs:
                value, is_error = ser.deserialize_or_error(obj)
                if is_error:
                    raise value
                out.append(self._maybe_device(value))
            return out
        if len(refs) == 1 and (refs[0].owner_address is None or
                               tuple(refs[0].owner_address) == self.address):
            # Owned single ref still pending: block this thread on the
            # completion event directly — the reply callback (loop thread)
            # sets it, one futex wake, no coroutine scheduling at all.
            ref = refs[0]
            t_block0 = time.monotonic()
            entry = self.memory_store.get_blocking(ref.id, timeout)
            if entry is None:
                raise GetTimeoutError(f"timed out resolving {ref}")
            if isinstance(entry, ser.SerializedObject):
                value, is_error = ser.deserialize_or_error(entry)
                if is_error:
                    raise value
                return [self._maybe_device(value)]
            if (isinstance(entry, ShmMarker)
                    and entry.node_id == self.node_id.binary()):
                obj = self.shm.get_serialized(ref.id)
                if obj is not None:
                    value, is_error = ser.deserialize_or_error(obj)
                    if is_error:
                        raise value
                    return [self._maybe_device(value)]
            # Remote/spilled/device entries: the async machinery owns those
            # — with only the REMAINING slice of the caller's budget (the
            # blocking wait above may already have consumed part of it, and
            # ray.get(timeout=T) must not block ~2T).
            if timeout is not None:
                timeout = max(0.0, timeout - (time.monotonic() - t_block0))
        coro = self._get_async(refs, timeout)
        outer = None if timeout is None else timeout + 5
        return self.loop_thread.run(coro, timeout=outer)

    async def _get_async(self, refs: List[ObjectRef],
                         timeout: Optional[float]) -> List[Any]:
        if len(refs) == 1:
            # gather() wraps each coroutine in a Task; skip that for the
            # ubiquitous single-ref get.
            results = [await self._resolve_ref(refs[0], timeout)]
        else:
            results = await asyncio.gather(
                *[self._resolve_ref(r, timeout) for r in refs])
        out = []
        for obj in results:
            value, is_error = ser.deserialize_or_error(obj)
            if is_error:
                raise value
            out.append(await self._maybe_device_async(value))
        return out

    async def _resolve_ref(self, ref: ObjectRef,
                           timeout: Optional[float]) -> ser.SerializedObject:
        deadline = None if timeout is None else time.monotonic() + timeout
        # 1. Local shm (covers all objects materialized on this node).
        obj = self.shm.get_serialized(ref.id)
        if obj is not None:
            return obj
        # 2. Owner memory store (locally-owned values or markers).
        entry = self.memory_store.get_if_exists(ref.id)
        if entry is None and (ref.owner_address is None
                              or tuple(ref.owner_address) == self.address):
            # We own it but it is still pending — wait for task completion.
            try:
                entry = await self.memory_store.get(
                    ref.id, None if deadline is None
                    else max(0.0, deadline - time.monotonic()))
            except asyncio.TimeoutError:
                raise GetTimeoutError(f"timed out resolving {ref}")
        if entry is not None:
            try:
                return await self._materialize(ref.id, entry, deadline)
            except ObjectLostError:
                # Owned object lost (node death / eviction): re-execute its
                # producing task from retained lineage (reference:
                # object_recovery_manager.h:43).
                obj = await self._recover_object(ref.id, deadline)
                if obj is not None:
                    return obj
                raise
        # 3. Borrowed: ask the owner.
        return await self._resolve_from_owner(ref, deadline)

    async def _recover_object(self, object_id: ObjectID,
                              deadline: Optional[float]
                              ) -> Optional[ser.SerializedObject]:
        """Lineage re-execution for a lost owned object. Returns the
        materialized object, or None when no lineage exists. Concurrent
        recoveries of the same object share one re-execution."""
        fut = self._recoveries.get(object_id)
        if fut is None:
            spec = self.task_manager.lineage_spec(object_id)
            if spec is None:
                return None
            logger.warning("object %s lost; re-executing %s from lineage",
                           object_id, spec.function_name)
            fut = asyncio.ensure_future(self._rerun_lineage(spec, object_id))
            self._recoveries[object_id] = fut

            def _cleanup(f, oid=object_id):
                if self._recoveries.get(oid) is f:
                    del self._recoveries[oid]

            fut.add_done_callback(_cleanup)
        await asyncio.shield(fut)
        entry = self.memory_store.get_if_exists(object_id)
        if entry is None:
            return None
        return await self._materialize(object_id, entry, deadline)

    async def _rerun_lineage(self, spec: TaskSpec, object_id: ObjectID) -> None:
        # Clear the stale marker so completion waits on the fresh result.
        self.memory_store.delete(object_id)
        self.task_manager.add_pending(spec)
        key = spec.scheduling_key()
        pool = self._lease_pools.get(key)
        if pool is None:
            pool = LeasePool(self, key, spec)
            self._lease_pools[key] = pool
        deps = self.unresolved_owned_deps(spec)
        if deps:
            await self.wait_owned_deps(deps)
        pool.queue.put_nowait(spec)
        pool.maybe_scale_up()
        await self.memory_store.get(object_id, None)

    async def _materialize(self, object_id: ObjectID, entry: Any,
                           deadline: Optional[float]) -> ser.SerializedObject:
        if isinstance(entry, ser.SerializedObject):
            return entry
        assert isinstance(entry, ShmMarker)
        if entry.node_id == self.node_id.binary() or self.shm.contains(object_id):
            obj = self.shm.get_serialized(object_id)
            if obj is not None:
                return obj
            obj = self.read_spilled(object_id)
            if obj is not None:
                return obj
            raise ObjectLostError(f"object {object_id} missing from local shm "
                                  "(evicted?)")
        return await self._fetch_remote(object_id, entry.node_id, deadline)

    async def _fetch_remote(self, object_id: ObjectID, node_id: bytes,
                            deadline: Optional[float]) -> ser.SerializedObject:
        """Pull an object from another node's store via its nodelet and cache
        it in local shm (reference: ObjectManager Pull, C12). Small objects
        arrive in one RPC; anything over object_transfer_chunk_bytes streams
        as concurrent chunk RPCs bounded by a per-process in-flight budget
        (pull admission — reference: pull_manager.h:49)."""
        nodes = await self.gcs_client.call("list_nodes")
        target = next((n for n in nodes if n["node_id"] == node_id), None)
        if target is None:
            raise ObjectLostError(f"node for object {object_id} is gone")
        cfg = get_config()
        # Same-host fast path: another nodelet's arena on THIS machine is
        # directly mappable — one memcpy out of tmpfs beats N chunk RPCs
        # (serialize + 2 socket crossings + reassembly per chunk). This is
        # the same-host half of the reference's Push/PullManager locality
        # (push_manager.h:27); genuinely-remote pulls take the chunk path
        # below, with peer chunk serving spreading the source load.
        if (cfg.object_transfer_same_host_arena
                and target.get("object_store_path")
                and tuple(target["address"])[0] == self.address[0]):
            obj = self._fetch_same_host_arena(
                object_id, target["object_store_path"])
            if obj is not None:
                try:
                    self.shm.put_serialized(object_id, obj)
                except Exception:
                    pass
                return obj
        t = None if deadline is None else deadline - time.monotonic()
        client = RpcClient(*target["address"], name="fetch")
        try:
            info = await client.call(
                "fetch_object_info", object_id=object_id.binary(),
                inline_below=cfg.object_transfer_chunk_bytes, timeout=t)
            if info is None:
                raise ObjectLostError(
                    f"object {object_id} not found on owner node")
            if "buffers" in info:
                # Small object: came back whole in the info reply (one RPC
                # total — the common path pays no extra round trip).
                obj = ser.SerializedObject(
                    info["metadata"], info["buffers"], [])
            else:
                obj = await self._fetch_chunked(
                    client, object_id, info, deadline)
        except (ConnectionLost, RemoteError, OSError) as e:
            # Node died faster than the GCS noticed — same as "gone".
            raise ObjectLostError(
                f"node holding {object_id} unreachable: {e!r}") from e
        finally:
            await client.close()
        try:
            self.shm.put_serialized(object_id, obj)
        except Exception:
            pass
        return obj

    async def _peer_chunk_client(self, addr: Tuple[str, int]) -> RpcClient:
        client = self._peer_chunk_clients.get(addr)
        if client is None:
            client = RpcClient(*addr, name="peer-chunk")
            self._peer_chunk_clients[addr] = client
        return client

    async def _rpc_peer_fetch_chunk(self, object_id: bytes, offset: int,
                                    length: int) -> Dict[str, Any]:
        """Serve one chunk of an object this worker holds (fully in shm,
        or partially mid-pull) to another puller the owner redirected
        here. {"missing": True} sends the peer back to the owner."""
        import pickle

        active = self._active_pulls.get(object_id)
        if active is not None:
            flat, done = active
            if offset in done:
                return {"data": pickle.PickleBuffer(
                    memoryview(flat)[offset:offset + length])}
        obj = self.shm.get_serialized(ObjectID(object_id))
        if obj is None:
            return {"missing": True}
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
        if not spans:
            return {"missing": True}
        if len(spans) == 1:
            return {"data": pickle.PickleBuffer(spans[0])}
        out = bytearray()
        for s in spans:
            out += s
        return {"data": pickle.PickleBuffer(out)}

    def _fetch_same_host_arena(self, object_id: ObjectID, store_path: str):
        """Read an object straight out of a same-host peer nodelet's shm
        arena (returns None -> caller falls back to the RPC pull). The
        returned buffers are pinned zero-copy views of the peer arena;
        the pin releases when the last consumer drops (and survives peer
        death: the mapping outlives an unlink)."""
        import os

        from ray_tpu.core.object_store import SharedMemoryStore

        if not os.path.exists(store_path):
            return None  # different machine/namespace after all
        cache = self.__dict__.setdefault("_peer_arenas", {})
        store = cache.get(store_path)
        if store is None:
            try:
                store = SharedMemoryStore(store_path, prefault=False)
            except OSError:
                return None
            cache[store_path] = store
        try:
            return store.get_serialized(object_id)
        except Exception:  # torn mapping (peer died mid-open): RPC path
            return None

    @property
    def _pull_sem(self) -> "asyncio.Semaphore":
        # Shared across every concurrent fetch in this process: the
        # admission budget is per puller, not per object.
        sem = self.__dict__.get("_pull_sem_obj")
        if sem is None:
            sem = asyncio.Semaphore(
                max(1, get_config().object_transfer_max_inflight_chunks))
            self.__dict__["_pull_sem_obj"] = sem
        return sem

    async def _fetch_chunked(self, client: RpcClient, object_id: ObjectID,
                             info: Dict[str, Any],
                             deadline: Optional[float]
                             ) -> ser.SerializedObject:
        cfg = get_config()
        chunk = cfg.object_transfer_chunk_bytes
        total = sum(info["sizes"])
        flat = bytearray(total)
        self._last_fetch_chunks = -(-total // chunk)  # test introspection
        # Peer chunk serving (reference: PushManager/PullManager chunk
        # machinery, push_manager.h:27): landed chunks are (a) reported to
        # the owner piggybacked on the next chunk request, so the owner
        # learns locations from pull acks, and (b) servable to other
        # pullers the owner redirects here — a broadcast becomes a chunk
        # distribution tree instead of N serial full pulls from one node.
        done: set = set()
        unreported: List[int] = []
        self._active_pulls[object_id.binary()] = (flat, done)
        self._fetch_redirects = getattr(self, "_fetch_redirects", 0)

        async def pull_from_peer(addr, off: int, length: int) -> bool:
            try:
                peer = await self._peer_chunk_client(tuple(addr))
                t = (None if deadline is None
                     else deadline - time.monotonic())
                reply = await peer.call(
                    "peer_fetch_chunk", object_id=object_id.binary(),
                    offset=off, length=length, timeout=t)
            except Exception:  # noqa: BLE001 - peer gone: owner fallback
                return False
            if not isinstance(reply, dict) or "data" not in reply:
                return False
            with memoryview(reply["data"]) as mv:
                if mv.nbytes != length:
                    return False
                flat[off:off + mv.nbytes] = mv
            self._fetch_redirects += 1
            return True

        async def pull_one(off: int) -> None:
            length = min(chunk, total - off)
            async with self._pull_sem:
                t = (None if deadline is None
                     else deadline - time.monotonic())
                have, unreported[:] = unreported[:], []
                reply = await client.call(
                    "fetch_object_chunk", object_id=object_id.binary(),
                    offset=off, length=length, timeout=t,
                    puller=list(self.address), have=have)
                if isinstance(reply, dict) and "redirect" in reply:
                    if not await pull_from_peer(
                            reply["redirect"], off, length):
                        reply = await client.call(
                            "fetch_object_chunk",
                            object_id=object_id.binary(), offset=off,
                            length=length, timeout=t, no_redirect=True)
                    else:
                        done.add(off)
                        unreported.append(off)
                        return
            if reply is None:
                raise ObjectLostError(
                    f"object {object_id} vanished mid-transfer")
            data = reply["data"] if isinstance(reply, dict) else reply
            with memoryview(data) as mv:
                flat[off:off + mv.nbytes] = mv
            done.add(off)
            unreported.append(off)

        tasks = [asyncio.ensure_future(pull_one(off))
                 for off in range(0, total, chunk)]
        try:
            await asyncio.gather(*tasks)
        except BaseException:
            # First failure: cancel siblings and drain them BEFORE the
            # caller closes the client — orphaned tasks would log
            # never-retrieved exceptions and pin the flat buffer.
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            self._active_pulls.pop(object_id.binary(), None)
            raise
        # Completed: peers now find the object in local shm (the caller
        # puts it there); drop the partial-pull registration.
        self._active_pulls.pop(object_id.binary(), None)
        # Zero-copy re-slice of the assembled bytes into the original
        # buffer boundaries (the views keep `flat` alive).
        buffers: List[Any] = []
        pos = 0
        view = memoryview(flat)
        for n in info["sizes"]:
            buffers.append(view[pos:pos + n])
            pos += n
        return ser.SerializedObject(info["metadata"], buffers, [])

    async def _resolve_from_owner(
        self, ref: ObjectRef, deadline: Optional[float]
    ) -> ser.SerializedObject:
        owner = tuple(ref.owner_address)
        client = RpcClient(*owner, name="owner")
        try:
            while True:
                t = None if deadline is None else max(
                    0.1, deadline - time.monotonic())
                try:
                    reply = await client.call(
                        "get_object", object_id=ref.id.binary(),
                        borrower=self.address, timeout=t)
                except asyncio.TimeoutError:
                    raise GetTimeoutError(f"timed out resolving {ref}")
                except (ConnectionLost, RemoteError) as e:
                    raise ObjectLostError(
                        f"owner of {ref} unreachable: {e!r}") from e
                kind = reply["kind"]
                if kind == "inline":
                    return ser.SerializedObject(
                        reply["metadata"], reply["buffers"], [])
                if kind == "shm":
                    if self.shm.contains(ref.id):
                        return self.shm.get_serialized(ref.id)
                    try:
                        return await self._fetch_remote(
                            ref.id, reply["node_id"], deadline)
                    except ObjectLostError:
                        # Ask the owner to recover it (lineage lives there).
                        reply = await client.call(
                            "get_object", object_id=ref.id.binary(),
                            borrower=self.address, recover=True, timeout=t)
                        if reply["kind"] == "inline":
                            return ser.SerializedObject(
                                reply["metadata"], reply["buffers"], [])
                        if reply["kind"] == "shm":
                            return await self._fetch_remote(
                                ref.id, reply["node_id"], deadline)
                        raise
                if kind == "pending":
                    await asyncio.sleep(0.02)
                    continue
                raise ObjectLostError(f"object {ref} lost: {reply.get('error')}")
        finally:
            await client.close()

    async def _ready_ref(self, ref: ObjectRef,
                         timeout: Optional[float]) -> None:
        """Readiness by metadata only (reference: wait_manager.h:30) — never
        pulls a remote payload; ray.wait on a large remote object must not
        move it."""
        deadline = None if timeout is None else time.monotonic() + timeout
        if self.shm.contains(ref.id):
            return
        entry = self.memory_store.get_if_exists(ref.id)
        if entry is None and (ref.owner_address is None
                              or tuple(ref.owner_address) == self.address):
            await self.memory_store.get(
                ref.id, None if deadline is None
                else max(0.0, deadline - time.monotonic()))
            return
        if entry is not None:
            return
        owner = tuple(ref.owner_address)
        client = RpcClient(*owner, name="owner-wait")
        try:
            while True:
                t = None if deadline is None else max(
                    0.1, deadline - time.monotonic())
                reply = await client.call(
                    "get_object", object_id=ref.id.binary(),
                    borrower=self.address, timeout=t)
                if reply["kind"] in ("inline", "shm"):
                    return
                if reply["kind"] == "pending":
                    await asyncio.sleep(0.02)
                    continue
                raise ObjectLostError(
                    f"object {ref} lost: {reply.get('error')}")
        finally:
            await client.close()

    def wait(self, refs: List[ObjectRef], num_returns: int,
             timeout: Optional[float]) -> Tuple[List[ObjectRef], List[ObjectRef]]:
        async def _wait():
            tasks = {
                asyncio.ensure_future(self._ready_ref(r, timeout)): r
                for r in refs
            }
            ready: List[ObjectRef] = []
            pending = set(tasks)
            deadline = None if timeout is None else time.monotonic() + timeout
            while pending and len(ready) < num_returns:
                t = None if deadline is None else max(0.0, deadline - time.monotonic())
                done, pending = await asyncio.wait(
                    pending, timeout=t, return_when=asyncio.FIRST_COMPLETED)
                if not done:
                    break
                for d in done:
                    # Ready = the object is fetchable. Application errors are
                    # stored as serialized error *values*, so resolution still
                    # succeeds for them; an exception here is an infrastructure
                    # failure (timeout, lost object, dead owner) = not ready.
                    if d.exception() is None:
                        ready.append(tasks[d])
            for p in pending:
                p.cancel()
            ready_set = {r.id for r in ready}
            not_ready = [r for r in refs if r.id not in ready_set]
            return ready, not_ready

        return self.loop_thread.run(_wait())

    def get_async(self, ref: ObjectRef) -> concurrent.futures.Future:
        return self.loop_thread.run_async(self._get_one(ref))

    async def _get_one(self, ref: ObjectRef) -> Any:
        obj = await self._resolve_ref(ref, None)
        value, is_error = ser.deserialize_or_error(obj)
        if is_error:
            raise value
        return await self._maybe_device_async(value)

    async def await_ref(self, ref: ObjectRef) -> Any:
        """Used by `await ref` inside async actors (same loop)."""
        return await self._get_one(ref)

    # ------------------------------------------------------------------
    # Submission: normal tasks
    # ------------------------------------------------------------------
    def _process_args(self, args: tuple, kwargs: dict) -> Tuple[list, dict]:
        cfg = get_config()

        def conv(a: Any) -> Any:
            # Ref args carry the ObjectRef object itself: the pending-task
            # spec pins it (owner keeps the value alive until the task
            # completes — reference: TaskManager lineage pinning), and
            # pickling the ref on the wire registers a borrow executor-side.
            if isinstance(a, ObjectRef):
                return ("ref", a)
            obj = ser.serialize(a)
            if obj.total_bytes() > cfg.max_inline_object_size:
                return ("ref", self.put(a))
            return ("value", obj)

        return [conv(a) for a in args], {k: conv(v) for k, v in kwargs.items()}

    def submit_task(
        self,
        fn: Any,
        args: tuple,
        kwargs: dict,
        num_returns: int = 1,
        resources: Optional[Dict[str, float]] = None,
        scheduling_strategy: Any = None,
        max_retries: Optional[int] = None,
        retry_exceptions: bool = False,
        runtime_env: Optional[Dict[str, Any]] = None,
        function_name: str = "",
        label_selector: Optional[Dict[str, str]] = None,
    ) -> List[ObjectRef]:
        from ray_tpu._private.labels import validate_label_selector

        validate_label_selector(label_selector)
        fn_key = self.function_manager.export(fn, self.job_id.hex())
        p_args, p_kwargs = self._process_args(args, kwargs)
        cfg = get_config()
        spec = TaskSpec(
            task_id=TaskID.for_task(self.job_id),
            job_id=self.job_id,
            task_type=TaskType.NORMAL_TASK,
            function_key=fn_key,
            function_name=function_name or getattr(fn, "__name__", "fn"),
            args=p_args,
            kwargs=p_kwargs,
            num_returns=num_returns,
            resources=(resources if isinstance(resources, ResourceSet)
                       else ResourceSet(resources or {"CPU": 1.0})),
            scheduling_strategy=scheduling_strategy or DefaultStrategy(),
            max_retries=cfg.task_max_retries if max_retries is None else max_retries,
            retry_exceptions=retry_exceptions,
            owner_address=self.address,
            runtime_env=_prepare_runtime_env(runtime_env,
                                              self._gcs_call_sync),
            label_selector=label_selector,
            trace_parent=_current_trace_parent(),
            submitted_ts=time.time(),
        )
        _m_tasks_submitted().inc()
        return_ids = self.task_manager.add_pending(spec)
        if num_returns == -1:
            from ray_tpu._private.generators import ObjectRefGenerator

            self.loop.call_soon_threadsafe(
                lambda: self._gen_state(spec.task_id))
            refs = [ObjectRefGenerator(spec.task_id, self)]
            return_ids = []
        else:
            refs = []
        for oid in return_ids:
            self.ref_counter.add_owned_ref(oid)
            refs.append(ObjectRef(oid, owner_address=self.address))

        # Coalesced handoff to the loop: one wakeup drains a whole submission
        # wave (a per-task call_soon_threadsafe self-pipe write would cost a
        # syscall per task).
        with self._submit_buf_lock:
            first = not self._submit_buf
            self._submit_buf.append(spec)
        if first:
            self.loop.call_soon_threadsafe(self._drain_submit_buf)
        return refs

    def _drain_submit_buf(self) -> None:
        with self._submit_buf_lock:
            specs, self._submit_buf = self._submit_buf, []
        touched = []
        for spec in specs:
            key = spec.scheduling_key()
            target_node = None
            if isinstance(spec.scheduling_strategy, SpreadStrategy):
                target_node = self._next_spread_node()
                if target_node is not None:
                    key = key + (target_node,)
            pool = self._lease_pools.get(key)
            if pool is None:
                pool = LeasePool(self, key, spec, target_node=target_node)
                self._lease_pools[key] = pool
            # Owner-side dependency resolution (reference:
            # dependency_resolver.h — a task is dispatched only once its args
            # exist). Without this, a dependent task batched together with
            # its upstream deadlocks: the executor blocks resolving the arg
            # while the upstream's result rides the same batch reply.
            deps = self.unresolved_owned_deps(spec)
            if deps:
                async def _when_ready(pool=pool, spec=spec, deps=deps):
                    await self.wait_owned_deps(deps)
                    pool.queue.put_nowait(spec)
                    pool.maybe_scale_up()

                asyncio.ensure_future(_when_ready())
            else:
                pool.queue.put_nowait(spec)
                if pool not in touched:
                    touched.append(pool)
        for pool in touched:
            pool.maybe_scale_up()
        self._update_lease_queue_gauge()

    def _update_lease_queue_gauge(self) -> None:
        """Submitter-side backlog awaiting a worker lease (runs on the loop
        thread at submit waves and lease-pump exits — cheap sum of qsizes)."""
        _m_lease_queue_gauge().set(
            float(sum(p.queue.qsize()
                      for p in self._lease_pools.values())),
            tags={"pid": str(os.getpid())})

    def _next_spread_node(self) -> Optional[bytes]:
        """Round-robin over the cached alive-node list (refreshed every 1s
        by a background loop started on first SPREAD submission)."""
        if not self._spread_refresh_started:
            self._spread_refresh_started = True

            async def _refresh_loop():
                while not self._shutdown:
                    try:
                        nodes = await self.gcs_client.call("list_nodes")
                        self._spread_nodes = [n["node_id"] for n in nodes
                                              if n["alive"]]
                    except Exception:
                        pass
                    await asyncio.sleep(1.0)

            asyncio.ensure_future(_refresh_loop())
        if not self._spread_nodes:
            return None
        self._spread_rr += 1
        return self._spread_nodes[self._spread_rr % len(self._spread_nodes)]

    async def actor_state(self, actor_id: ActorID, *,
                          refresh: bool = False,
                          wait_change: Optional[float] = None
                          ) -> Optional[Dict[str, Any]]:
        """Cached actor info from the GCS pubsub subscription. refresh=True
        bootstraps with one get_actor RPC (the subscription may have started
        after the actor's transitions); wait_change waits for the next push
        before re-reading the cache."""
        if not self._actor_sub_started:
            self._actor_sub_started = True
            asyncio.ensure_future(self._actor_pubsub_loop())
        if wait_change is not None:
            pulse = self._actor_pulse
            try:
                await asyncio.wait_for(pulse.wait(), wait_change)
                cached = self._actor_states.get(actor_id.hex())
                if cached is not None:
                    return cached
            except asyncio.TimeoutError:
                pass  # no push: fall through to an RPC refresh (pubsub is
                # an optimization, not the source of truth)
            refresh = True
        if not refresh:
            cached = self._actor_states.get(actor_id.hex())
            if cached is not None:
                return cached
        info = await self.gcs_client.call("get_actor",
                                          actor_id=actor_id.binary())
        if info is not None:
            self._actor_states[actor_id.hex()] = info
        return info

    def start_log_subscriber(self) -> None:
        """Driver side of the log pipeline (reference: log_monitor.py tails →
        GCS pubsub → driver stdout): consume the 'logs' channel and echo
        worker output with a (source, node=…) prefix.

        Known limit: workers here are pooled per runtime-env, not per job, so
        lines are not job-tagged — with several concurrent drivers each one
        echoes the whole cluster's worker output (the reference filters on
        job_id, log_monitor.py)."""
        if self._log_sub_started:
            return
        self._log_sub_started = True
        self.loop.call_soon_threadsafe(
            lambda: self.loop.create_task(self._log_sub_loop()))

    async def _subscribe(self, channel: str, cursor: int, retry_s: float):
        """Long-poll one GCS pub/sub channel from `cursor`; yields each
        answer's messages. The cursor follows the answer's last sequence,
        never the highest seen: a restarted GCS counts from 1 again and
        replays its backlog once to a cursor from its previous incarnation
        (`PubsubChannels.poll`), and a subscriber that held the old, higher
        number would be answered at once with that whole backlog on every
        poll until the new count passed the old."""
        while not self._shutdown:
            self._pubsub_cursors[channel] = cursor
            try:
                out = await self.gcs_client.call(
                    "pubsub_poll", cursors={channel: cursor}, timeout=40.0)
            except Exception:
                await asyncio.sleep(retry_s)
                continue
            msgs = (out or {}).get(channel)
            if msgs:
                cursor = msgs[-1][0]
                yield [m for _, m in msgs]

    async def _log_sub_loop(self) -> None:
        import sys

        # Subscribe from "now": cursor 0 would replay every retained log
        # batch from jobs that ran before this driver connected.
        try:
            cursor = await self.gcs_client.call("pubsub_seq", channel="logs")
        except Exception:
            cursor = 0
        async for msgs in self._subscribe("logs", cursor, retry_s=1.0):
            for batches in msgs:
                for b in batches:
                    prefix = f"({b.get('source')}, node={b.get('node')})"
                    for line in b.get("lines", []):
                        print(f"{prefix} {line}", file=sys.stderr, flush=True)

    async def _actor_pubsub_loop(self) -> None:
        """Long-poll the GCS 'actors' channel (reference: the reference's
        pubsub had zero subscribers in round 1 — this makes actor-state
        discovery push-based)."""
        async for msgs in self._subscribe("actors", 0, retry_s=0.5):
            for msg in msgs:
                view = msg.get("actor") or {}
                aid = view.get("actor_id")
                if aid:
                    self._actor_states[aid] = view
            pulse, self._actor_pulse = self._actor_pulse, asyncio.Event()
            pulse.set()

    def unresolved_owned_deps(self, spec: TaskSpec) -> List[ObjectID]:
        """Top-level ref args owned by this process whose values are not yet
        available. (Borrowed refs resolve against their remote owner at
        execution time and cannot deadlock on our own reply pipeline.)"""
        deps: List[ObjectID] = []
        for a in list(spec.args) + list(spec.kwargs.values()):
            if a[0] != "ref":
                continue
            r = a[1]
            if (r.owner_address is not None
                    and tuple(r.owner_address) != self.address):
                continue
            if (self.memory_store.get_if_exists(r.id) is None
                    and not self.shm.contains(r.id)):
                deps.append(r.id)
        return deps

    async def wait_owned_deps(self, deps: List[ObjectID]) -> None:
        await asyncio.gather(
            *[self.memory_store.get(d, None) for d in deps])

    async def push_task_batch_to(self, client: RpcClient,
                                 addr: Tuple[str, int],
                                 specs: List[TaskSpec]) -> bool:
        """Push a batch of tasks in one RPC. Returns False when the worker is
        unusable (connection lost) so the caller drops the lease. Failed
        specs are retried or failed permanently, mirroring push_task_to."""
        if len(specs) == 1:
            return await self.push_task_to(client, addr, specs[0])
        now = time.time()
        for spec in specs:
            spec.lease_ts = now  # LEASE_GRANTED: a leased worker took it
            self.task_manager.mark_inflight(spec.task_id, addr)
        _fr.note_batch("task", len(specs))
        rec = _fr.maybe_begin_call(specs[0].function_name)
        try:
            reply = await client.call(
                "push_task_batch", specs=specs,
                timeout=86400.0, fr_rec=rec)
            replies = reply["replies"]
        except (ConnectionLost, RemoteError, asyncio.TimeoutError, OSError) as e:
            for spec in specs:
                retry_spec = self.task_manager.fail_or_retry(spec.task_id)
                if retry_spec is not None:
                    pool = self._lease_pools.get(spec.scheduling_key())
                    if pool is not None:
                        pool.queue.put_nowait(retry_spec)
                        pool.maybe_scale_up()
                else:
                    err = WorkerCrashedError(
                        f"task {spec.function_name} failed: worker died ({e!r})")
                    self.task_manager.fail_permanently(
                        spec.task_id, ser.serialize_error(err))
            return not isinstance(e, (ConnectionLost, OSError))
        except Exception as e:
            logger.exception("push_task_batch failed locally")
            for spec in specs:
                self.task_manager.fail_permanently(
                    spec.task_id, ser.serialize_error(e))
            return True
        t0 = time.perf_counter_ns() if rec is not None else 0
        for spec, item in zip(specs, replies):
            await self.handle_task_reply(spec, item)
        if rec is not None:
            _fr.finish_call_from_reply(
                rec, reply, time.perf_counter_ns() - t0)
        return True

    async def push_task_to(self, client: RpcClient, addr: Tuple[str, int],
                           spec: TaskSpec) -> bool:
        """Push one task to a leased worker. Returns False when the worker is
        unusable (connection lost) so the caller drops the lease."""
        spec.lease_ts = time.time()  # LEASE_GRANTED: a leased worker took it
        self.task_manager.mark_inflight(spec.task_id, addr)
        rec = _fr.maybe_begin_call(spec.function_name)
        try:
            reply = await client.call("push_task", spec=spec,
                                      timeout=86400.0, fr_rec=rec)
        except (ConnectionLost, RemoteError, asyncio.TimeoutError, OSError) as e:
            retry_spec = self.task_manager.fail_or_retry(spec.task_id)
            if retry_spec is not None:
                logger.info("retrying task %s after %r", spec.task_id, e)
                pool = self._lease_pools.get(spec.scheduling_key())
                if pool is not None:
                    pool.queue.put_nowait(retry_spec)
                    pool.maybe_scale_up()
            else:
                err = WorkerCrashedError(
                    f"task {spec.function_name} failed: worker died ({e!r})")
                self.task_manager.fail_permanently(
                    spec.task_id, ser.serialize_error(err))
            return not isinstance(e, (ConnectionLost, OSError))
        except Exception as e:
            # Unexpected local failure (e.g. a spec that won't serialize must
            # fail the task, not strand it forever in PENDING).
            logger.exception("push_task failed locally for %s", spec.task_id)
            self.task_manager.fail_permanently(
                spec.task_id, ser.serialize_error(e))
            return True
        t0 = time.perf_counter_ns() if rec is not None else 0
        await self.handle_task_reply(spec, reply)
        if rec is not None:
            _fr.finish_call_from_reply(
                rec, reply, time.perf_counter_ns() - t0)
        return True

    def handle_task_reply_fast(self, spec: TaskSpec,
                               reply: Dict[str, Any]) -> bool:
        """Synchronous reply handling for the common case (no borrows, no
        device objects, not cancelled/generator, no retryable error).
        Returns False to send the reply through the full async path."""
        if (reply.get("borrows") or reply.get("device_objects")
                or reply.get("cancelled") or "generator_count" in reply):
            return False
        results = []
        for item in reply["results"]:
            kind = item[0]
            if kind == "inline":
                results.append(ser.SerializedObject(item[1], item[2], []))
            elif kind == "shm":
                results.append(ShmMarker(item[1]))
            elif kind == "error":
                if spec.retry_exceptions:
                    return False
                results.append(
                    ser.SerializedObject(ser.METADATA_ERROR, [item[1]], []))
            else:
                return False
        self.task_manager.complete(spec.task_id, results)
        self._observe_task_done(spec)
        return True

    async def handle_task_reply(self, spec: TaskSpec, reply: Dict[str, Any]) -> None:
        # Synchronous borrow handoff (reference: task replies carry borrowed_refs
        # so the owner registers the executor as borrower BEFORE dropping the
        # spec's arg pins — closes the free-vs-late-add race). If we are not
        # the owner of a ref we passed along (we borrowed it ourselves),
        # forward the registration to the true owner on the executor's behalf.
        if reply.get("borrows"):
            b = tuple(reply["borrower"])
            if b != self.address:
                owners: Dict[ObjectID, Any] = {}
                for a in list(spec.args) + list(spec.kwargs.values()):
                    if a[0] == "ref":
                        owners[a[1].id] = a[1].owner_address
                    else:
                        for r in getattr(a[1], "nested_refs", None) or []:
                            owners[r.id] = r.owner_address
                forward: Dict[Tuple[str, int], List[bytes]] = {}
                for ob in reply["borrows"]:
                    oid = ObjectID(ob)
                    owner = owners.get(oid)
                    if owner is None or tuple(owner) == self.address:
                        self.ref_counter.add_borrower(oid, b)
                    else:
                        forward.setdefault(tuple(owner), []).append(ob)
                for owner, obs in forward.items():
                    client = None
                    try:
                        client = RpcClient(*owner, name="borrow-forward")
                        await client.notify(
                            "update_borrows", borrower=list(b),
                            ops=[("add", ob) for ob in obs])
                    except Exception:
                        pass  # executor's own 1s add report is the fallback
                    finally:
                        if client is not None:
                            try:
                                await client.close()
                            except Exception:
                                pass
        for ob, src in (reply.get("device_objects") or {}).items():
            # Owner-side record for the free protocol: when this return ref's
            # count hits zero we must tell the source actor to drop its HBM
            # copy (on_owner_ref_zero in experimental/device_objects.py).
            self.device_object_srcs[ob] = tuple(src)
        if reply.get("cancelled"):
            self.task_manager.fail_permanently(
                spec.task_id,
                ser.serialize_error(TaskCancelledError(str(spec.task_id))))
            return
        if "generator_count" in reply:
            # Streaming task finished: the items were delivered via
            # report_generator_item; here we only learn the final length.
            st = self._gen_state(spec.task_id)
            st.count = reply["generator_count"]
            st.pulse()
            self.task_manager.complete(spec.task_id, [])
            self._observe_task_done(spec)
            return
        results = []
        for item in reply["results"]:
            kind = item[0]
            if kind == "inline":
                results.append(ser.SerializedObject(item[1], item[2], []))
            elif kind == "shm":
                results.append(ShmMarker(item[1]))
            elif kind == "error":
                err_obj = ser.SerializedObject(ser.METADATA_ERROR, [item[1]], [])
                if spec.retry_exceptions:
                    retry_spec = self.task_manager.fail_or_retry(spec.task_id)
                    if retry_spec is not None:
                        pool = self._lease_pools.get(spec.scheduling_key())
                        if pool is not None:
                            pool.queue.put_nowait(retry_spec)
                            pool.maybe_scale_up()
                        return
                results.append(err_obj)
        self.task_manager.complete(spec.task_id, results)
        self._observe_task_done(spec)

    # ------------------------------------------------------------------
    # Submission: actors
    # ------------------------------------------------------------------
    def create_actor(
        self,
        cls: Any,
        args: tuple,
        kwargs: dict,
        resources: Optional[Dict[str, float]] = None,
        name: str = "",
        max_restarts: int = 0,
        max_task_retries: int = 0,
        max_concurrency: int = 1,
        detached: bool = False,
        runtime_env: Optional[Dict[str, Any]] = None,
        scheduling_strategy: Any = None,
        get_if_exists: bool = False,
        label_selector: Optional[Dict[str, str]] = None,
    ) -> ActorID:
        from ray_tpu._private.labels import validate_label_selector

        validate_label_selector(label_selector)
        actor_id = ActorID.of(self.job_id)
        cls_key = self.function_manager.export(cls, self.job_id.hex())
        p_args, p_kwargs = self._process_args(args, kwargs)
        spec = TaskSpec(
            task_id=TaskID.for_actor_creation(actor_id),
            job_id=self.job_id,
            task_type=TaskType.ACTOR_CREATION_TASK,
            function_key=cls_key,
            function_name=getattr(cls, "__name__", "Actor") + ".__init__",
            args=p_args,
            kwargs=p_kwargs,
            num_returns=0,
            resources=ResourceSet(resources or {"CPU": 1.0}),
            scheduling_strategy=scheduling_strategy or DefaultStrategy(),
            owner_address=self.address,
            actor_id=actor_id,
            max_concurrency=max_concurrency,
            max_restarts=max_restarts,
            max_task_retries=max_task_retries,
            runtime_env=_prepare_runtime_env(runtime_env,
                                              self._gcs_call_sync),
            label_selector=label_selector,
            trace_parent=_current_trace_parent(),
            submitted_ts=time.time(),
        )
        register = self.gcs_client.call_retrying(
            "register_actor",
            actor_id=actor_id.binary(),
            creation_spec=ser_spec(spec),
            name=name,
            max_restarts=max_restarts,
            detached=detached,
            get_if_exists=get_if_exists,
        )
        if name or get_if_exists:
            # The reply decides which actor the handle refers to: block.
            reply = self.loop_thread.run(register)
            if not reply.get("ok"):
                raise ValueError(
                    reply.get("error", "actor registration failed"))
            if reply.get("existing_actor_id"):
                return ActorID(reply["existing_actor_id"])
            return actor_id
        # Anonymous actors: creation is ASYNCHRONOUS, like the reference's
        # actor-creation task — the handle returns immediately and N
        # creations pipeline through the GCS instead of paying N serial
        # round-trips (the dominant term in actor churn). A registration
        # failure poisons the local state cache so pending calls raise
        # instead of waiting on an actor that never existed.

        async def _register():
            try:
                reply = await register
            except Exception as e:  # noqa: BLE001
                reply = {"ok": False, "error": repr(e)}
            finally:
                self._registering_actors.discard(actor_id.hex())
            if not reply.get("ok"):
                logger.warning("async actor registration failed: %s",
                               reply.get("error"))
                self._actor_states[actor_id.hex()] = {
                    "state": "DEAD",
                    "error": reply.get("error",
                                       "actor registration failed"),
                }
                self._actor_pulse.set()
                self._actor_pulse.clear()

        # Mark in flight BEFORE scheduling: the first actor task can race
        # the registration RPC, and its _ensure_client must read
        # get_actor -> None as pending, not dead (registration-race fix).
        self._registering_actors.add(actor_id.hex())
        asyncio.run_coroutine_threadsafe(_register(), self.loop)
        return actor_id

    def submit_actor_task(
        self,
        actor_id: ActorID,
        method_name: str,
        args: tuple,
        kwargs: dict,
        num_returns: int = 1,
        max_task_retries: int = 0,
        concurrency_group: str = "",
        tensor_transport: str = "",
    ) -> List[ObjectRef]:
        with self._task_counter_lock:
            seq = self._actor_seq_nos.get(actor_id, 0)
            self._actor_seq_nos[actor_id] = seq + 1
        p_args, p_kwargs = self._process_args(args, kwargs)
        spec = TaskSpec(
            task_id=TaskID.for_actor_task(actor_id, seq),
            job_id=self.job_id,
            task_type=TaskType.ACTOR_TASK,
            function_key="",
            function_name=method_name,
            args=p_args,
            kwargs=p_kwargs,
            num_returns=num_returns,
            resources=ResourceSet({}),
            scheduling_strategy=DefaultStrategy(),
            owner_address=self.address,
            actor_id=actor_id,
            actor_method_name=method_name,
            seq_no=seq,
            concurrency_group=concurrency_group,
            tensor_transport=tensor_transport,
            trace_parent=_current_trace_parent(),
            submitted_ts=time.time(),
        )
        _m_tasks_submitted().inc()
        return_ids = self.task_manager.add_pending(spec)
        if num_returns == -1:
            from ray_tpu._private.generators import ObjectRefGenerator

            self.loop.call_soon_threadsafe(
                lambda: self._gen_state(spec.task_id))
            refs = [ObjectRefGenerator(spec.task_id, self)]
            return_ids = []
        else:
            refs = []
        for oid in return_ids:
            self.ref_counter.add_owned_ref(oid)
            refs.append(ObjectRef(oid, owner_address=self.address))

        def _submit():
            sub = self._actor_submitters.get(actor_id)
            if sub is None:
                sub = ActorSubmitter(self, actor_id)
                self._actor_submitters[actor_id] = sub
            sub.enqueue(spec, max_task_retries)

        self.loop.call_soon_threadsafe(_submit)
        return refs

    # ------------------------------------------------------------------
    # Execution side (runs in worker processes)
    # ------------------------------------------------------------------
    async def _rpc_push_task(self, spec) -> Dict[str, Any]:
        t_entry = time.perf_counter_ns() if _fr._ENABLED else 0
        if isinstance(spec, (bytes, bytearray, memoryview)):
            spec = deser_spec(spec)
        loop = asyncio.get_running_loop()
        reply = await loop.run_in_executor(
            self._task_executor, self._execute_task_sync, spec)
        if t_entry and isinstance(reply, dict):
            # Server-total stamp (_frs): receipt -> reply ready. The client
            # stitches dispatch = _frs - exec into its sampled record.
            reply["_frs"] = time.perf_counter_ns() - t_entry
        return reply

    async def _rpc_push_task_batch(self, specs: List[TaskSpec]) -> Dict[str, Any]:
        """Execute a batch of normal tasks (one RPC frame per submitter
        pipeline window). The whole batch runs in ONE executor hop — a
        thread handoff per task would dominate short tasks; cross-batch
        concurrency still comes from the submitter's pipeline window landing
        multiple batches on different executor threads."""
        loop = asyncio.get_running_loop()

        def run_batch():
            return [self._execute_task_sync(
                deser_spec(s) if isinstance(s, bytes) else s)
                for s in specs]

        t_entry = time.perf_counter_ns() if _fr._ENABLED else 0
        replies = await loop.run_in_executor(self._task_executor, run_batch)
        out: Dict[str, Any] = {"replies": replies}
        if t_entry:
            out["_frs"] = time.perf_counter_ns() - t_entry
        return out

    async def _rpc_create_actor(self, creation_spec: bytes) -> Dict[str, Any]:
        spec = deser_spec(creation_spec)
        # The actor __init__ runs on the actor executor thread, NOT on the
        # event loop: creation fetches the class from GCS and resolves args,
        # both of which block on loop-driven IO (deadlock if run on the loop).
        self._actor_executors[""] = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, spec.max_concurrency), thread_name_prefix="actor")
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._actor_executors[""], self._create_actor_sync, spec)

    def _create_actor_sync(self, spec: TaskSpec) -> Dict[str, Any]:
        try:
            cls = self.function_manager.fetch(spec.function_key)
            args, kwargs = self._resolve_spec_args_sync(spec)
            instance = cls(*args, **kwargs)
            self._actor_instance = instance
            self._actor_creation_spec = spec
            self._actor_is_async = any(
                asyncio.iscoroutinefunction(getattr(cls, m, None))
                for m in dir(cls) if not m.startswith("__")
            )
            if not self._actor_is_async and spec.max_concurrency <= 1:
                self._start_fast_lane()
            return {"ok": True}
        except BaseException as e:
            logger.exception("actor creation failed")
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}

    # ------------------------------------------------------------------
    # Actor fast lane.
    #
    # Motivation (measured on the 1-core bench host): a sync actor call
    # through the asyncio server costs 6 thread/process wakeups — driver
    # loop → worker loop → executor thread → worker loop → driver loop —
    # and each wake is ~50-200µs of scheduler latency, putting the floor
    # near 800µs/call. A single-threaded sync actor doesn't need any of
    # that: one blocking thread can read→execute→reply with ZERO
    # intra-worker hops. The asyncio plane stays authoritative for
    # everything else (creation, cancel, generators via delegation,
    # health checks). Reference contrast: core_worker's direct actor call
    # path has the same shape (dedicated execution thread fed by the RPC
    # plane) but its hop costs ~10µs in C++; ours is a redesign that
    # removes the hop instead of cheapening it.
    # ------------------------------------------------------------------
    def _start_fast_lane(self) -> None:
        import socket as _socket

        lsock = _socket.socket()
        lsock.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        lsock.bind((self.server.host, 0))
        lsock.listen(16)
        self._fast_lane_port = lsock.getsockname()[1]
        self._actor_exec_lock = threading.Lock()

        def accept_loop() -> None:
            while not self._shutdown:
                try:
                    conn, _ = lsock.accept()
                except OSError:
                    return
                conn.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
                t = threading.Thread(
                    target=self._serve_fast_lane_conn, args=(conn,),
                    name="fast-lane", daemon=True)
                t.start()

        threading.Thread(target=accept_loop, name="fast-lane-accept",
                         daemon=True).start()

    def _serve_fast_lane_conn(self, conn) -> None:
        from ray_tpu._private.rpc import (
            KIND_RESPONSE, recv_frame_blocking, send_frame_blocking)

        try:
            while not self._shutdown:
                kind, msg_id, (method, kwargs) = recv_frame_blocking(conn)
                t_entry = time.perf_counter_ns() if _fr._ENABLED else 0
                try:
                    if method == "push_actor_task":
                        reply = self._fast_lane_execute(kwargs["spec"])
                    elif method == "push_actor_task_batch":
                        reply = {"replies": [
                            self._fast_lane_execute(s)
                            for s in kwargs["specs"]]}
                    elif method == "ping":
                        reply = {"ok": True}
                    else:
                        raise RuntimeError(
                            f"method {method!r} not supported on fast lane")
                    if t_entry and isinstance(reply, dict):
                        reply["_frs"] = time.perf_counter_ns() - t_entry
                    send_frame_blocking(conn, KIND_RESPONSE, msg_id,
                                        (True, reply))
                except BaseException as e:  # noqa: BLE001
                    send_frame_blocking(conn, KIND_RESPONSE, msg_id,
                                        (False, e))
        except Exception:
            pass  # disconnect: the submitter reconnects/retries
        finally:
            try:
                conn.close()
            except Exception:
                pass

    def _fast_lane_execute(self, spec) -> Dict[str, Any]:
        if isinstance(spec, (bytes, bytearray, memoryview)):
            spec = deser_spec(spec)  # legacy frame shape
        if spec.actor_method_name == "__dag_channel_loop__":
            # Never on the fast lane: the loop replies only at teardown and
            # this connection is strictly sequential (the submitter routes
            # loops via the control lane; this is a guard).
            return {"results": [self._error_result(RuntimeError(
                "__dag_channel_loop__ must use the control lane"))]}
        method = getattr(self._actor_instance, spec.actor_method_name, None)
        if method is None:
            return {"results": [self._error_result(AttributeError(
                f"actor has no method {spec.actor_method_name!r}"))] *
                max(1, spec.num_returns)}
        # Mutual exclusion with the asyncio-plane executor thread: other
        # handles (borrowers, other drivers) may still push through the
        # normal lane concurrently.
        with self._actor_exec_lock:
            return self._execute_actor_task_sync(spec, method)

    async def _rpc_fast_lane_info(self) -> Dict[str, Any]:
        return {"port": getattr(self, "_fast_lane_port", None)}

    async def _rpc_dag_method_info(self, method_name: str) -> Dict[str, Any]:
        """Compile-time probe for CompiledDAG channel mode: the driver must
        reject stages whose methods are async (a pinned sync loop would get
        an un-awaited coroutine back)."""
        m = getattr(self._actor_instance, method_name, None)
        return {"exists": m is not None,
                "is_async": bool(m is not None
                                 and asyncio.iscoroutinefunction(m))}

    def _dag_channel_loop(self, in_descs: List[Dict[str, Any]],
                          out_descs: List[Dict[str, Any]],
                          method_name: str) -> str:
        """Pinned compiled-DAG stage loop (reference: aDAG's per-actor
        execution loops, dag/compiled_dag_node.py): read one value per
        input channel (fan-in, arg order), run the method, write the
        result to every output channel (fan-out) — zero control-plane RPCs
        per item on same-host edges; cross-host edges ride RpcChannels.
        Exits when any input channel closes (dag.teardown). Runs on an
        executor thread; the per-item exec lock keeps max_concurrency=1
        semantics against fast-lane calls."""
        from ray_tpu.dag import _DagChannelError
        from ray_tpu.experimental.channel import rpc_channel
        from ray_tpu.experimental.channel.shm_channel import ChannelClosed

        ins = [rpc_channel.open_reader(self, d) for d in in_descs]
        outs = [rpc_channel.open_writer(self, d) for d in out_descs]
        lock = getattr(self, "_actor_exec_lock", None)
        method = getattr(self._actor_instance, method_name)
        try:
            while True:
                try:
                    values = [c.read() for c in ins]
                except ChannelClosed:
                    return "closed"
                try:
                    err = next((v for v in values
                                if isinstance(v, _DagChannelError)), None)
                    if err is not None:
                        out: Any = err  # upstream failed: propagate
                    elif lock is not None:
                        with lock:
                            out = method(*values)
                    else:
                        out = method(*values)
                except BaseException as e:  # noqa: BLE001
                    out = _DagChannelError(e)
                payload = None
                for c in outs:
                    try:
                        if payload is None:
                            payload = c.encode(out)  # once per item,
                            # however many consumers (fan-out)
                        c.write_payload(payload)
                    except ChannelClosed:
                        return "closed"
                    except Exception as e:  # noqa: BLE001
                        # Unserializable / slot-overflow result: surface
                        # the real cause downstream instead of dying with
                        # an opaque ChannelClosed.
                        c.write(_DagChannelError(e))
        finally:
            for c in outs:
                try:
                    c.close()
                except Exception:
                    pass
                try:
                    c.destroy()  # rpc writers: drop registry + client
                except Exception:
                    pass
            for c in ins:
                try:
                    # destroy: shm in-channels are this loop's to unlink
                    # (their reader created them); rpc readers just close
                    # and drop their registry entry.
                    c.destroy()
                except Exception:
                    pass

    async def _rpc_push_actor_task_batch(self, specs: List[TaskSpec]) -> Dict[str, Any]:
        """Execute a batch of actor tasks. Runs of consecutive sync methods
        collapse into one executor hop (ordering preserved — same thread, in
        order); async methods interleave via gather as before."""
        t_entry = time.perf_counter_ns() if _fr._ENABLED else 0
        decoded = [deser_spec(s) if isinstance(s, bytes) else s
                   for s in specs]
        if t_entry:
            for s in decoded:
                s.__dict__["_t_entry"] = t_entry  # as _sched_key: unshipped
        loop = asyncio.get_running_loop()

        def is_batchable_sync(spec: TaskSpec):
            # Collapsing a run onto one thread serializes it — only legal
            # when the actor is single-threaded anyway (max_concurrency=1);
            # a concurrent actor's sync methods may block on each other.
            if (self._actor_instance is None or spec.concurrency_group
                    or (self._actor_creation_spec is not None
                        and self._actor_creation_spec.max_concurrency > 1)):
                return None
            m = getattr(self._actor_instance, spec.actor_method_name, None)
            if m is None or asyncio.iscoroutinefunction(m):
                return None
            return m

        futs: List[Any] = []
        sizes: List[int] = []
        i = 0
        while i < len(decoded):
            method = is_batchable_sync(decoded[i])
            if method is None:
                futs.append(asyncio.ensure_future(
                    self._rpc_push_actor_task_decoded(decoded[i])))
                sizes.append(1)
                i += 1
                continue
            run: List[Tuple[TaskSpec, Any]] = [(decoded[i], method)]
            j = i + 1
            while j < len(decoded):
                m = is_batchable_sync(decoded[j])
                if m is None:
                    break
                run.append((decoded[j], m))
                j += 1

            def run_sync(items=run):
                return [self._execute_actor_task_locked(s, m)
                        for s, m in items]

            futs.append(loop.run_in_executor(self._actor_executors[""],
                                             run_sync))
            sizes.append(len(run))
            i = j
        results = await asyncio.gather(*futs)
        replies: List[Dict[str, Any]] = []
        for size, res in zip(sizes, results):
            if size == 1 and isinstance(res, dict):
                replies.append(res)
            else:
                replies.extend(res)
        out: Dict[str, Any] = {"replies": replies}
        if t_entry:
            out["_frs"] = time.perf_counter_ns() - t_entry
        return out

    async def _rpc_push_actor_task(self, spec: TaskSpec) -> Dict[str, Any]:
        t_entry = time.perf_counter_ns() if _fr._ENABLED else 0
        if os.environ.get("RAY_TPU_PUSH_TRACE"):
            t0 = time.perf_counter_ns()
            if isinstance(spec, (bytes, bytearray, memoryview)):
                spec = deser_spec(spec)
            spec.__dict__["_t_entry"] = t_entry
            t1 = time.perf_counter_ns()
            reply = await self._rpc_push_actor_task_decoded(spec)
            t2 = time.perf_counter_ns()
            reply["_trace"] = {"entry": t0, "decoded": t1, "done": t2}
            if t_entry:
                reply["_frs"] = time.perf_counter_ns() - t_entry
            return reply
        if isinstance(spec, (bytes, bytearray, memoryview)):
            spec = deser_spec(spec)
        spec.__dict__["_t_entry"] = t_entry
        reply = await self._rpc_push_actor_task_decoded(spec)
        if t_entry and isinstance(reply, dict):
            reply["_frs"] = time.perf_counter_ns() - t_entry
        return reply

    async def _rpc_push_actor_task_decoded(
            self, task_spec: TaskSpec) -> Dict[str, Any]:
        if self._actor_instance is None:
            return {"results": [self._error_result(
                ActorDiedError("actor instance not initialized"))] *
                max(1, task_spec.num_returns)}
        if task_spec.actor_method_name == "__dag_channel_loop__":
            # Dedicated thread: the loop runs until dag.teardown, and
            # parking it on the shared '' executor (max_workers=1 for
            # mc=1 actors) would starve every other normal-lane execution.
            loop = asyncio.get_running_loop()
            ex = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="dag-loop")
            try:
                return await loop.run_in_executor(
                    ex, self._execute_actor_task_sync,
                    task_spec, self._dag_channel_loop)
            finally:
                ex.shutdown(wait=False)
        method = getattr(self._actor_instance, task_spec.actor_method_name, None)
        if method is None:
            return {"results": [self._error_result(AttributeError(
                f"actor has no method {task_spec.actor_method_name!r}"))] *
                max(1, task_spec.num_returns)}
        if asyncio.iscoroutinefunction(method):
            args, kwargs = await self._resolve_spec_args(task_spec)
            try:
                self._current_task_id = task_spec.task_id
                result = await method(*args, **kwargs)
                return self._reply_results(task_spec, result)
            except BaseException as e:  # noqa: BLE001
                return {"results": [self._error_result(e)] *
                        max(1, task_spec.num_returns)}
            finally:
                self._current_task_id = None
        loop = asyncio.get_running_loop()
        if task_spec.concurrency_group:
            # Named concurrency groups get their own single-thread lane
            # (reference: actor concurrency groups), created lazily per
            # group name. Like the dag-loop thread above, they bypass the
            # exec lock on purpose: a parked long-poll in a group must not
            # serialize against — or starve — normal-lane execution on a
            # max_concurrency=1 actor.
            executor = self._actor_executors.get(task_spec.concurrency_group)
            if executor is None:
                executor = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"cg-{task_spec.concurrency_group}")
                self._actor_executors[task_spec.concurrency_group] = executor
            return await loop.run_in_executor(
                executor, self._execute_actor_task_sync, task_spec, method)
        executor = self._actor_executors[""]
        if os.environ.get("RAY_TPU_PUSH_TRACE"):
            tpre = time.perf_counter_ns()
            reply = await loop.run_in_executor(
                executor, self._execute_actor_task_locked, task_spec, method)
            reply["_trace_hop"] = {
                "pre_hop": tpre, "post_hop": time.perf_counter_ns()}
            return reply
        return await loop.run_in_executor(
            executor, self._execute_actor_task_locked, task_spec, method)

    def _execute_actor_task_locked(self, spec: TaskSpec,
                                   method: Any) -> Dict[str, Any]:
        """Normal-lane execution, serialized against the fast lane when one
        is active (both lanes may receive tasks for the same
        max_concurrency=1 actor from different handles)."""
        lock = getattr(self, "_actor_exec_lock", None)
        if lock is None:
            return self._execute_actor_task_sync(spec, method)
        with lock:
            return self._execute_actor_task_sync(spec, method)

    def _execute_actor_task_sync(self, spec: TaskSpec, method: Any) -> Dict[str, Any]:
        t0 = time.time()
        ok = True
        args_ready_ts = None
        trace_tok = _enter_trace_context(spec)
        try:
            texec = (time.perf_counter_ns()
                     if os.environ.get("RAY_TPU_PUSH_TRACE") else 0)
            args, kwargs = self._resolve_spec_args_sync(spec)
            args_ready_ts = time.time()
            self._current_task_id = spec.task_id
            _fr.enter_task(spec.__dict__.get("_t_entry", 0))
            t_exec = time.perf_counter_ns() if _fr._ENABLED else 0
            result = method(*args, **kwargs)
            t_done = time.perf_counter_ns() if t_exec else 0
            if spec.num_returns == -1:
                return self._stream_generator(spec, iter(result))
            reply = self._reply_results(spec, result)
            if t_exec:
                # Exec-only stamp (_frx): user code, excluding arg
                # resolution (charged to dispatch) and result packing.
                reply["_frx"] = t_done - t_exec
                _fr.note_exec(spec.function_name, t_done - t_exec)
            if texec:
                reply["_trace_exec"] = {
                    "exec_start": texec, "exec_end": time.perf_counter_ns()}
            return reply
        except BaseException as e:  # noqa: BLE001
            ok = False
            return {"results": [self._error_result(e)] * max(1, spec.num_returns)}
        finally:
            self._current_task_id = None
            _exit_trace_context(trace_tok)
            self.record_task_event(spec, t0, time.time(), ok, args_ready_ts)

    def _execute_task_sync(self, spec: TaskSpec) -> Dict[str, Any]:
        if spec.task_id in self._cancelled_tasks:
            self._cancelled_tasks.discard(spec.task_id)
            return {"cancelled": True, "results": []}
        t0 = time.time()
        ok = True
        args_ready_ts = None
        trace_tok = _enter_trace_context(spec)
        try:
            fn = self.function_manager.fetch(spec.function_key)
            args, kwargs = self._resolve_spec_args_sync(spec)
            args_ready_ts = time.time()
            self._current_task_id = spec.task_id
            t_exec = time.perf_counter_ns() if _fr._ENABLED else 0
            result = fn(*args, **kwargs)
            t_done = time.perf_counter_ns() if t_exec else 0
            if spec.num_returns == -1:
                return self._stream_generator(spec, iter(result))
            reply = self._reply_results(spec, result)
            if t_exec:
                reply["_frx"] = t_done - t_exec
                _fr.note_exec(spec.function_name, t_done - t_exec)
            return reply
        except BaseException as e:  # noqa: BLE001
            ok = False
            logger.info("task %s raised: %r", spec.function_name, e)
            return {"results": [self._error_result(e)] * max(1, spec.num_returns)}
        finally:
            self._current_task_id = None
            _exit_trace_context(trace_tok)
            self.record_task_event(spec, t0, time.time(), ok, args_ready_ts)

    def _spec_arg_ref_ids(self, spec: TaskSpec) -> List[ObjectID]:
        """ObjectIDs referenced by this task's args (direct ref args and
        refs nested inside value args)."""
        out: List[ObjectID] = []
        for a in list(spec.args) + list(spec.kwargs.values()):
            if a[0] == "ref":
                out.append(a[1].id)
            else:
                for r in getattr(a[1], "nested_refs", None) or []:
                    out.append(r.id)
        return out

    def _with_borrows(self, spec: TaskSpec, reply: Dict[str, Any]) -> Dict[str, Any]:
        """Attach this executor's arg-ref borrows to a task reply. The owner
        registers them synchronously; if the user code did not actually keep
        the refs, our report loop sends the remove once the spec is dropped."""
        ids = self._spec_arg_ref_ids(spec)
        if ids:
            reply["borrows"] = [o.binary() for o in ids]
            reply["borrower"] = self.address
        return reply

    def _resolve_spec_args_sync(self, spec: TaskSpec) -> Tuple[list, dict]:
        # Fast path: no ref args → pure deserialization, skip the loop hop.
        if (all(a[0] == "value" for a in spec.args)
                and all(v[0] == "value" for v in spec.kwargs.values())):
            return ([self._maybe_device(ser.deserialize(a[1]))
                     for a in spec.args],
                    {k: self._maybe_device(ser.deserialize(v[1]))
                     for k, v in spec.kwargs.items()})
        return self.loop_thread.run(self._resolve_spec_args(spec))

    async def _resolve_spec_args(self, spec: TaskSpec) -> Tuple[list, dict]:
        async def one(a):
            if a[0] == "value":
                return await self._maybe_device_async(ser.deserialize(a[1]))
            ref = a[1]
            obj = await self._resolve_ref(ref, None)
            value, is_error = ser.deserialize_or_error(obj)
            if is_error:
                raise value
            return await self._maybe_device_async(value)

        args = [await one(a) for a in spec.args]
        kwargs = {k: await one(v) for k, v in spec.kwargs.items()}
        return args, kwargs

    def _reply_results(self, spec: TaskSpec, result: Any) -> Dict[str, Any]:
        reply: Dict[str, Any] = {}
        reply["results"] = self._pack_results(spec, result, reply)
        return self._with_borrows(spec, reply)

    def _pack_results(self, spec: TaskSpec, result: Any,
                      reply: Optional[Dict[str, Any]] = None) -> List[Any]:
        if spec.num_returns == 0:
            return []
        values = (result,) if spec.num_returns == 1 else tuple(result)
        if spec.num_returns > 1 and len(values) != spec.num_returns:
            raise ValueError(
                f"task declared num_returns={spec.num_returns} but returned "
                f"{len(values)} values")
        cfg = get_config()
        if spec.tensor_transport == "device":
            # Returns stay in this process's HBM; only the skeleton travels
            # (experimental/device_objects.py store_result).
            from ray_tpu.experimental import device_objects as devobj

            wrapped = []
            for i, v in enumerate(values):
                oid = ObjectID.for_task_return(spec.task_id, i)
                wrapped.append(devobj.store_result(self, oid, v))
                if reply is not None:
                    reply.setdefault("device_objects", {})[oid.binary()] = \
                        tuple(self.address)
            values = tuple(wrapped)
        out = []
        for i, v in enumerate(values):
            obj = ser.serialize(v)
            if obj.total_bytes() > cfg.max_inline_object_size:
                oid = ObjectID.for_task_return(spec.task_id, i)
                self.put_shm_or_spill(oid, obj)
                out.append(("shm", self.node_id.binary()))
            else:
                out.append(("inline", obj.metadata,
                            ser.wire_buffers(obj.buffers)))
        return out

    # ------------------------------------------------------------------
    # Streaming generators (reference: ReportGeneratorItemReturns,
    # task_manager.h:168; see _private/generators.py for the protocol)
    # ------------------------------------------------------------------
    def _gen_state(self, task_id: TaskID):
        from ray_tpu._private.generators import GeneratorState

        st = self._generators.get(task_id)
        if st is None:
            st = GeneratorState()
            self._generators[task_id] = st
        return st

    async def _rpc_report_generator_item(
            self, task_id: bytes, index: Optional[int] = None,
            item: Optional[Tuple] = None,
            count: Optional[int] = None) -> Dict[str, Any]:
        """Owner side: store one streamed item (or just answer a
        backpressure probe when item is None)."""
        tid = TaskID(task_id)
        st = self._gen_state(tid)
        if item is not None and index is not None:
            oid = ObjectID.for_task_return(tid, index)
            kind = item[0]
            if kind == "inline":
                self.memory_store.put(
                    oid, ser.SerializedObject(item[1], item[2], []))
            elif kind == "shm":
                self.memory_store.put(oid, ShmMarker(item[1]))
            elif kind == "error":
                self.memory_store.put(oid, ser.SerializedObject(
                    ser.METADATA_ERROR, [item[1]], []))
            self.ref_counter.add_owned_ref(oid)
            st.reported = max(st.reported, index + 1)
            if _fr._ENABLED:
                st.landed[index] = time.perf_counter_ns()
        if count is not None:
            st.count = count
        st.pulse()
        if not _fr._ENABLED:
            return {"unconsumed": st.reported - st.consumed}
        # The owner's account of the stream so far rides the reply the
        # producer blocks on anyway: it ends in `ray_tpu.stream.sent`.
        return {"unconsumed": st.reported - st.consumed,
                "held_ns": st.held_ns, "held_max_ns": st.held_max_ns,
                "starved_ns": st.starved_ns}

    async def gen_next(self, task_id: TaskID,
                       idx: int) -> Optional[ObjectID]:
        """Owner side: wait until item idx exists (returns its ObjectID) or
        the stream is known to have ended before idx (returns None). One
        clock read says who was late: an item that lay here first was held
        for the consumer, a consumer that asked first was starved by the
        producer until the item landed."""
        st = self._gen_state(task_id)
        asked = time.perf_counter_ns() if _fr._ENABLED else 0
        while True:
            if idx < st.reported:
                st.consumed = max(st.consumed, idx + 1)
                landed = st.landed.pop(idx, 0)
                if landed and asked:
                    if landed <= asked:
                        held = asked - landed
                        st.held_ns += held
                        if held > st.held_max_ns:
                            st.held_max_ns = held
                    else:
                        st.starved_ns += landed - asked
                return ObjectID.for_task_return(task_id, idx)
            if st.count is not None and idx >= st.count:
                return None
            await st.wait()

    def _stream_generator(self, spec: TaskSpec, gen) -> Dict[str, Any]:
        """Executor side: ship each yielded value to the owner as its own
        object. Runs on the task executor thread; every report is a blocking
        RPC (transport backpressure) plus a pause while the owner holds too
        many unconsumed items.

        The thread's time is split where it goes (inside the user's
        generator, serializing, blocked on the owner's reply, paused):
        clock reads and additions per item, and one `ray_tpu.stream.sent`
        mark a stream, which also carries the owner's account of the same
        stream as its last reply gave it."""
        cfg = get_config()
        owner = tuple(spec.owner_address)
        idx = 0
        laps = _fr.laps("body", "serialize", "report", "paused")
        nbytes = report_max = unconsumed_max = 0
        last: Dict[str, Any] = {}  # the owner's newest reply

        def too_many(reply) -> bool:
            """The owner holds more unconsumed items than it should."""
            return (reply is not None and reply.get("unconsumed", 0)
                    > cfg.generator_backpressure_num_objects)

        try:
            for value in gen:
                laps.lap("body")
                obj = ser.serialize(value)
                size = obj.total_bytes()
                if size > cfg.max_inline_object_size:
                    oid = ObjectID.for_task_return(spec.task_id, idx)
                    self.put_shm_or_spill(oid, obj)
                    item: Tuple = ("shm", self.node_id.binary())
                else:
                    item = ("inline", obj.metadata,
                            ser.wire_buffers(obj.buffers))
                nbytes += size
                laps.lap("serialize")
                reply = self._send_gen_item(owner, spec.task_id, idx, item)
                idx += 1
                report_max = max(report_max, laps.lap("report"))
                if reply is not None:
                    unconsumed_max = max(unconsumed_max,
                                         reply.get("unconsumed", 0))
                if too_many(reply):
                    while too_many(reply):
                        time.sleep(0.02)
                        reply = self._send_gen_item(owner, spec.task_id,
                                                    None, None)
                    laps.lap("paused")
                last = reply or last
            laps.lap("body")
        except BaseException as e:  # noqa: BLE001
            laps.lap("body")
            err = self._error_result(e)
            last = self._send_gen_item(owner, spec.task_id, idx, err) or last
            idx += 1
            laps.lap("report")
        _fr.mark("ray_tpu.stream.sent", rid=_fr.request_id(),
                 task=spec.task_id.hex(), items=idx, bytes=nbytes,
                 report_max_ms=report_max / 1e6,
                 unconsumed_max=unconsumed_max,
                 held_ms=last.get("held_ns", 0) / 1e6,
                 held_max_ms=last.get("held_max_ns", 0) / 1e6,
                 starved_ms=last.get("starved_ns", 0) / 1e6, **laps.ms())
        return {"results": [], "generator_count": idx}

    def _send_gen_item(self, owner: Tuple[str, int], task_id: TaskID,
                       index: Optional[int], item: Optional[Tuple]):
        async def _send():
            client = self._gen_clients.get(owner)
            if client is None:
                client = RpcClient(*owner, name="gen-report")
                self._gen_clients[owner] = client
            return await client.call(
                "report_generator_item", task_id=task_id.binary(),
                index=index, item=item, timeout=600.0)

        try:
            return asyncio.run_coroutine_threadsafe(
                _send(), self.loop).result(timeout=620)
        except Exception:
            return None  # owner gone: keep draining the generator cheaply

    def _error_result(self, exc: BaseException) -> Tuple:
        tb = traceback.format_exc()
        err = RayTaskError(f"{type(exc).__name__}: {exc}", cause=exc,
                           traceback_str=tb)
        obj = ser.serialize_error(err)
        return ("error", obj.buffers[0])

    # ------------------------------------------------------------------
    # Object-plane RPC handlers (owner side)
    # ------------------------------------------------------------------
    async def _rpc_get_object(
        self, object_id: bytes, borrower: Optional[Tuple[str, int]] = None,
        recover: bool = False,
    ) -> Dict[str, Any]:
        oid = ObjectID(object_id)
        if borrower:
            self.ref_counter.add_borrower(oid, tuple(borrower))
        if recover:
            # Borrower observed the object's node gone — re-execute lineage
            # before answering (owner-driven recovery).
            try:
                await self._recover_object(oid, None)
            except Exception:
                pass
        entry = self.memory_store.get_if_exists(oid)
        if entry is None:
            if self.shm.contains(oid):
                return {"kind": "shm", "node_id": self.node_id.binary()}
            if self.task_manager.get_spec(oid.task_id()) is not None:
                return {"kind": "pending"}
            return {"kind": "lost", "error": "unknown object"}
        if isinstance(entry, ShmMarker):
            return {"kind": "shm", "node_id": entry.node_id}
        return {"kind": "inline", "metadata": entry.metadata,
                "buffers": ser.wire_buffers(entry.buffers)}

    async def _rpc_wait_object(self, object_id: bytes,
                               timeout: float = 30.0) -> bool:
        oid = ObjectID(object_id)
        try:
            await self.memory_store.get(oid, timeout)
            return True
        except asyncio.TimeoutError:
            return self.shm.contains(oid)

    async def _rpc_update_borrows(self, borrower: Tuple[str, int],
                                  ops: List[Tuple[str, bytes]]) -> None:
        """Ordered add/remove batch from one borrower (order preserves
        remove-then-readd sequences)."""
        b = tuple(borrower)
        for op, ob in ops:
            if op == "add":
                self.ref_counter.add_borrower(ObjectID(ob), b)
            else:
                self.ref_counter.remove_borrower(ObjectID(ob), b)

    async def _rpc_check_borrows(self, object_ids: List[bytes]) -> List[bytes]:
        """Audit reply: which of these objects do we still hold refs to."""
        return [ob for ob in object_ids
                if self.ref_counter.holds_local_ref(ObjectID(ob))]

    async def _rpc_free_objects(self, object_ids: List[bytes]) -> None:
        for ob in object_ids:
            oid = ObjectID(ob)
            self.memory_store.delete(oid)
            try:
                self.shm.delete(oid)
            except Exception:
                pass

    async def _rpc_cancel_task(self, task_id: bytes) -> bool:
        tid = TaskID(task_id)
        self._cancelled_tasks.add(tid)
        return True

    async def _rpc_exit_worker(self) -> bool:
        logger.info("exit_worker received; shutting down pid %d", os.getpid())
        loop = asyncio.get_running_loop()
        loop.call_later(0.05, os._exit, 0)
        return True

    async def _rpc_ping(self) -> str:
        return "pong"

    async def _cancel_pending(self, spec: TaskSpec,
                              force: bool = False) -> None:
        """Cancel a pending/running task (reference: CoreWorker::CancelTask).
        Non-force flags the executor so the task is skipped if it hasn't
        started. force=True additionally KILLS the executing worker process
        (the only way to stop arbitrary running Python, matching the
        reference's force_kill) — the lease/reap machinery cleans up."""
        pt_addr = None
        with self.task_manager._lock:
            pt = self.task_manager._pending.get(spec.task_id)
            if pt is not None:
                pt_addr = pt.inflight_on
        if pt_addr is not None:
            client = None
            try:
                client = RpcClient(*pt_addr, name="cancel")
                await client.call("cancel_task", task_id=spec.task_id.binary(),
                                  timeout=5)
                if force:
                    await client.notify("exit_worker")
            except Exception:
                pass
            finally:
                if client is not None:
                    try:
                        await client.close()
                    except Exception:
                        pass
        self.task_manager.fail_permanently(
            spec.task_id,
            ser.serialize_error(TaskCancelledError(spec.function_name)))

    # ------------------------------------------------------------------
    async def _borrow_report_loop(self) -> None:
        while not self._shutdown:
            await asyncio.sleep(1.0)
            await self._flush_borrow_reports()

    async def _flush_borrow_reports(self) -> None:
        # Serialized: report order is part of the borrow protocol (a
        # requeued 'add' must never be overtaken by its 'remove'), so a
        # caller-triggered flush must not interleave with the loop's.
        lock = self.__dict__.setdefault("_borrow_flush_lock",
                                        asyncio.Lock())
        async with lock:
            await self._flush_borrow_reports_locked()

    async def _flush_borrow_reports_locked(self) -> None:
        reports = self.ref_counter.drain_borrow_reports()
        for owner, ops in reports.items():
            if owner == self.address:
                continue
            client = None
            try:
                client = RpcClient(*owner, name="borrow-report")
                await client.notify(
                    "update_borrows", borrower=self.address,
                    ops=[(op, o.binary()) for op, o in ops])
            except Exception:
                # Transient failure must not lose protocol state: a lost add
                # frees under a live borrower, a lost remove pins forever.
                self.ref_counter.requeue_borrow_reports(owner, ops)
            finally:
                if client is not None:
                    try:
                        await client.close()
                    except Exception:
                        pass

    async def _borrower_audit_loop(self) -> None:
        """Owner side: reconcile borrower sets against reality so a borrower
        that died (or whose removal report was lost) doesn't pin our objects
        forever (reference: WaitForRefRemoved, reference_count.h:73).

        A borrow is only dropped after it is observed missing/unreachable in
        two consecutive rounds — one blip (network or check-then-act with an
        in-flight task carrying the ref) must not free a live object."""
        misses: Dict[Tuple[Tuple[str, int], ObjectID], int] = {}
        while not self._shutdown:
            await asyncio.sleep(5.0)
            snapshot = self.ref_counter.borrower_snapshot()
            seen: set = set()
            for borrower, oids in snapshot.items():
                if borrower == self.address:
                    continue
                client = None
                try:
                    client = RpcClient(*borrower, name="borrow-audit")
                    held = await client.call(
                        "check_borrows",
                        object_ids=[o.binary() for o in oids], timeout=10)
                    held_set = {bytes(h) for h in held}
                except Exception:
                    held_set = set()  # unreachable this round
                finally:
                    if client is not None:
                        try:
                            await client.close()
                        except Exception:
                            pass
                for oid in oids:
                    key = (borrower, oid)
                    seen.add(key)
                    if oid.binary() in held_set:
                        misses.pop(key, None)
                        continue
                    misses[key] = misses.get(key, 0) + 1
                    if misses[key] >= 2:
                        misses.pop(key, None)
                        self.ref_counter.remove_borrower(oid, borrower)
            # Drop miss counters for borrows that no longer exist.
            for key in [k for k in misses if k not in seen]:
                del misses[key]
