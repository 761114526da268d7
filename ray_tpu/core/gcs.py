"""GCS — the cluster control plane.

Counterpart of src/ray/gcs/gcs_server/ (C21–C23 in SURVEY.md §2.1): node
manager, actor manager (FSM with restarts), job manager, internal KV, function
store, placement groups, long-poll pub/sub, health checks, and the cluster
resource view. One asyncio process; tables in memory (a persistence hook mirrors
the reference's pluggable StoreClient so a Redis-style backend can slot in).

Redesign notes: the reference runs ~11 gRPC services on one asio loop; here one
RpcServer serves the union of handler methods. Actor scheduling leases workers
from nodelets exactly like normal-task scheduling does (reference:
gcs_actor_scheduler.h:115).
"""

from __future__ import annotations

import asyncio
import os
import pickle
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.backoff import Backoff, delay_for_attempt
from ray_tpu._private.chaos import get_chaos
from ray_tpu._private.ids import ActorID, JobID, NodeID, PlacementGroupID
from ray_tpu._private.rpc import RpcClient, RpcServer
from ray_tpu._private.task_spec import ResourceSet
from ray_tpu.utils.config import get_config
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)


# ---------------------------------------------------------------------------
# Pub/sub: long-poll channels (reference: src/ray/pubsub/, O(#subscribers)
# long-poll connections rather than O(#objects)).
# ---------------------------------------------------------------------------
class PubsubChannels:
    def __init__(self):
        self._messages: Dict[str, List[Tuple[int, Any]]] = {}
        self._seq: Dict[str, int] = {}
        self._cond = asyncio.Condition()
        self.max_backlog = 10_000

    async def publish(self, channel: str, message: Any) -> None:
        async with self._cond:
            seq = self._seq.get(channel, 0) + 1
            self._seq[channel] = seq
            backlog = self._messages.setdefault(channel, [])
            backlog.append((seq, message))
            if len(backlog) > self.max_backlog:
                del backlog[: len(backlog) // 2]
            self._cond.notify_all()

    async def poll(
        self, cursors: Dict[str, int], timeout: float = 30.0
    ) -> Dict[str, List[Tuple[int, Any]]]:
        """Return messages newer than each channel's cursor; blocks until
        something arrives or timeout."""
        deadline = time.monotonic() + timeout

        def _collect() -> Dict[str, List[Tuple[int, Any]]]:
            out: Dict[str, List[Tuple[int, Any]]] = {}
            for channel, cursor in cursors.items():
                if cursor > self._seq.get(channel, 0):
                    # Subscriber cursor from a previous GCS incarnation
                    # (sequences reset on restart): replay from the start.
                    cursor = 0
                msgs = [m for m in self._messages.get(channel, []) if m[0] > cursor]
                if msgs:
                    out[channel] = msgs
            return out

        async with self._cond:
            while True:
                out = _collect()
                if out:
                    return out
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {}
                try:
                    await asyncio.wait_for(self._cond.wait(), remaining)
                except asyncio.TimeoutError:
                    return {}


# ---------------------------------------------------------------------------
# Tables
# ---------------------------------------------------------------------------
class NodeInfo:
    def __init__(self, node_id: NodeID, address: Tuple[str, int],
                 resources: Dict[str, float], object_store_path: str,
                 labels: Dict[str, str]):
        self.node_id = node_id
        self.address = address
        self.resources_total = dict(resources)
        self.resources_available = dict(resources)
        self.object_store_path = object_store_path
        self.labels = labels
        self.alive = True
        self.last_heartbeat = time.monotonic()
        # unsatisfied lease shapes from the latest heartbeat (autoscaler
        # task-demand signal)
        self.demand: List[Dict[str, float]] = []


ACTOR_PENDING = "PENDING_CREATION"
ACTOR_ALIVE = "ALIVE"
ACTOR_RESTARTING = "RESTARTING"
ACTOR_DEAD = "DEAD"


class ActorInfo:
    def __init__(self, actor_id: ActorID, creation_spec: Any, name: str,
                 max_restarts: int, detached: bool):
        self.actor_id = actor_id
        self.creation_spec = creation_spec  # pickled TaskSpec bytes
        self.name = name
        self.max_restarts = max_restarts
        self.detached = detached
        self.state = ACTOR_PENDING
        self.address: Optional[Tuple[str, int]] = None
        self.node_id: Optional[NodeID] = None
        self.num_restarts = 0
        self.death_cause: str = ""

    def public_view(self) -> Dict[str, Any]:
        return {
            "actor_id": self.actor_id.hex(),
            "state": self.state,
            "name": self.name,
            "address": self.address,
            "node_id": self.node_id.hex() if self.node_id else None,
            "num_restarts": self.num_restarts,
            "death_cause": self.death_cause,
        }

    def to_state(self) -> Dict[str, Any]:
        return {
            "actor_id": self.actor_id.binary(),
            "creation_spec": self.creation_spec,
            "name": self.name,
            "max_restarts": self.max_restarts,
            "detached": self.detached,
            "state": self.state,
            "address": self.address,
            "node_id": self.node_id.binary() if self.node_id else None,
            "num_restarts": self.num_restarts,
            "death_cause": self.death_cause,
        }

    @staticmethod
    def from_state(state: Dict[str, Any]) -> "ActorInfo":
        info = ActorInfo(ActorID(state["actor_id"]), state["creation_spec"],
                         state["name"], state["max_restarts"],
                         state["detached"])
        info.state = state["state"]
        info.address = (tuple(state["address"])
                        if state["address"] else None)
        info.node_id = (NodeID(state["node_id"])
                        if state["node_id"] else None)
        info.num_restarts = state["num_restarts"]
        info.death_cause = state["death_cause"]
        return info


class PlacementGroupInfo:
    def __init__(self, pg_id: PlacementGroupID, bundles: List[Dict[str, float]],
                 strategy: str, name: str):
        self.pg_id = pg_id
        self.bundles = bundles
        self.strategy = strategy
        self.name = name
        self.state = "PENDING"
        # bundle index -> node_id
        self.bundle_nodes: Dict[int, NodeID] = {}

    def to_state(self) -> Dict[str, Any]:
        return {
            "pg_id": self.pg_id.binary(),
            "bundles": self.bundles,
            "strategy": self.strategy,
            "name": self.name,
            "state": self.state,
            "bundle_nodes": {i: n.binary()
                             for i, n in self.bundle_nodes.items()},
        }

    @staticmethod
    def from_state(state: Dict[str, Any]) -> "PlacementGroupInfo":
        info = PlacementGroupInfo(
            PlacementGroupID(state["pg_id"]), state["bundles"],
            state["strategy"], state["name"])
        info.state = state["state"]
        info.bundle_nodes = {int(i): NodeID(n)
                             for i, n in state["bundle_nodes"].items()}
        return info


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------
class GcsStorage:
    """Debounce layer over a pluggable StoreClient (reference:
    gcs/store_client/ — store_client.h contract, redis_store_client.h for
    external-store head-node FT). Backend by path: *.sqlite → row-wise
    incremental sqlite (WAL), anything else → atomic whole-snapshot
    pickle. Mutations mark dirty, a flush loop writes ≤1x per interval,
    shutdown flushes synchronously."""

    def __init__(self, path: Optional[str]):
        from ray_tpu.core.store_client import create_store_client

        self.path = path
        self.dirty = False
        try:
            self.client = create_store_client(path)
        except Exception:
            # A corrupt/garbage store file must not take down the control
            # plane it exists to protect: set it aside and start fresh
            # (same contract as an unreadable pickle snapshot).
            logger.exception("GCS store unusable; starting fresh")
            try:
                os.replace(path, path + ".corrupt")
                self.client = create_store_client(path)
            except Exception:
                self.client = None

    def load(self) -> Optional[Dict[str, Any]]:
        if self.client is None:
            return None
        try:
            return self.client.load()
        except Exception:
            logger.exception("GCS store unreadable; starting fresh")
            return None

    def save(self, tables: Dict[str, Any]) -> None:
        if self.client is None:
            return
        # Chaos seam: an injected failure here must leave dirty=True so
        # the flush loop retries (exactly the contract a full disk or a
        # killed store process exercises).
        get_chaos().failpoint("gcs.snapshot_save")
        self.client.save(tables)
        self.dirty = False


class GcsServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 persist_path: Optional[str] = None):
        self.server = RpcServer(host, port)
        self.pubsub = PubsubChannels()
        self.nodes: Dict[NodeID, NodeInfo] = {}
        self.actors: Dict[ActorID, ActorInfo] = {}
        # kill_actor arrivals for ids not registered yet (client-side
        # async actor creation): the late registration lands dead.
        # id -> tombstone time; TTL + size cap bound it (repeated kills of
        # bogus ids, or registrations that never arrive, must not grow it
        # forever). Insertion-ordered, so eviction drops the oldest.
        self._prekilled: Dict[ActorID, float] = {}
        self.named_actors: Dict[str, ActorID] = {}
        self.placement_groups: Dict[PlacementGroupID, PlacementGroupInfo] = {}
        self.kv: Dict[str, bytes] = {}
        self.jobs: Dict[int, Dict[str, Any]] = {}
        self._job_counter = 0
        self._nodelet_clients: Dict[NodeID, RpcClient] = {}
        self._background: List[asyncio.Task] = []
        self._actor_locks: Dict[ActorID, asyncio.Lock] = {}
        self._spread_rr = 0
        from collections import deque

        self.task_events: "deque" = deque(maxlen=20_000)
        # Last-write times of live metrics:* snapshots (hygiene scan input).
        self._metrics_seen: Dict[str, float] = {}
        self.storage = GcsStorage(persist_path)
        # Durable export-event files for external ingestion (reference:
        # src/ray/util/event.h + export_*.proto; gated by config).
        from ray_tpu._private.export_events import get_export_logger

        export_dir = (os.path.dirname(persist_path) if persist_path
                      else os.path.join("/tmp/ray_tpu", "default"))
        self.export = get_export_logger(export_dir)
        self._restore()

    def _export_event(self, source_type: str,
                      data: Dict[str, Any]) -> None:
        if self.export is not None:
            try:
                self.export.emit(source_type, data)
            except Exception:  # noqa: BLE001
                pass  # export is observability, never control flow

    def _restore(self) -> None:
        snap = self.storage.load()
        if not snap:
            return
        self.kv = snap.get("kv", {})
        self.jobs = {int(k): v for k, v in snap.get("jobs", {}).items()}
        self._job_counter = snap.get("job_counter", 0)
        self.named_actors = {n: ActorID(a)
                             for n, a in snap.get("named_actors", {}).items()}
        actors = snap.get("actors", [])
        # actors persist row-wise ({id_hex: state}) for incremental
        # backends; accept the old list form for pre-existing snapshots.
        states = actors.values() if isinstance(actors, dict) else actors
        for state in states:
            info = ActorInfo.from_state(state)
            self.actors[info.actor_id] = info
        for state in snap.get("placement_groups", {}).values():
            pg = PlacementGroupInfo.from_state(state)
            self.placement_groups[pg.pg_id] = pg
        logger.info("GCS restored %d actors, %d pgs, %d kv keys",
                    len(self.actors), len(self.placement_groups),
                    len(self.kv))

    def mark_dirty(self) -> None:
        self.storage.dirty = True

    def _snapshot_tables(self) -> Dict[str, Any]:
        return {
            # metrics:* snapshots are live telemetry from (possibly dead)
            # processes — persisting them would resurrect stale counters
            # after a GCS restart and inflate every merged total.
            "kv": {k: v for k, v in self.kv.items()
                   if not k.startswith("metrics:")},
            "jobs": {str(k): v for k, v in self.jobs.items()},
            "job_counter": self._job_counter,
            "named_actors": {n: a.binary()
                             for n, a in self.named_actors.items()},
            # row-wise so incremental backends rewrite only changed actors
            "actors": {a.actor_id.hex(): a.to_state()
                       for a in self.actors.values()},
            # committed PGs survive a GCS restart (reference: PGs live in
            # the Redis-backed store); nodelets re-report bundle holds via
            # heartbeat reconciliation either way.
            "placement_groups": {p.pg_id.hex(): p.to_state()
                                 for p in self.placement_groups.values()},
        }

    async def _persist_loop(self) -> None:
        while True:
            await asyncio.sleep(0.25)
            if self.storage.dirty:
                try:
                    self.storage.save(self._snapshot_tables())
                except Exception:
                    logger.exception("GCS snapshot failed")

    async def start(self) -> Tuple[str, int]:
        for name in dir(self):
            if name.startswith("rpc_"):
                self.server.register(name[4:], getattr(self, name))
        addr = await self.server.start()
        self._background.append(asyncio.ensure_future(self._health_check_loop()))
        self._background.append(asyncio.ensure_future(self._pg_retry_loop()))
        if self.storage.path:
            self._background.append(
                asyncio.ensure_future(self._persist_loop()))
        # Metrics: the GCS IS the KV store, so its registry flushes write
        # straight into the table (no RPC, no Worker). The write hops onto
        # the event loop — GCS tables are loop-thread-owned, and a direct
        # insert from the flusher thread would race _snapshot_tables'
        # iteration ("dictionary changed size during iteration").
        from ray_tpu.util import metrics as um

        loop = asyncio.get_running_loop()
        um.set_flush_sink(lambda key, payload: loop.call_soon_threadsafe(
            self._metrics_kv_put, key, payload))
        self._background.append(asyncio.ensure_future(self._metrics_loop()))
        # Flight recorder: lag-sample the GCS loop — a stalled GCS loop
        # delays every heartbeat/lease in the cluster, exactly the stall
        # the sampler exists to attribute.
        from ray_tpu._private import flight_recorder as _fr

        _fr.attach_loop(loop, "gcs")
        logger.info("GCS listening on %s:%d", *addr)
        return addr

    # Metric-snapshot hygiene (all on the loop thread). A process stale
    # for METRICS_TTL_S has its snapshot RETIRED: gauges drop (stale by
    # definition) while counters/histograms park under a per-origin
    # `metrics:_retired:<origin>` key — counters must stay monotonic in
    # /metrics, and keeping the parked copy per-origin means a process
    # that merely lost connectivity supersedes it on its next flush
    # instead of being double counted. Parked copies older than
    # METRICS_RETIRE_FOLD_S fold into one accumulator key to bound growth;
    # the fold gives up the supersede protection, so it waits a day — a
    # process that reconnects after a >24h partition (and somehow outlived
    # node health checks) may double count, a trade we accept to keep the
    # key space bounded on high-churn clusters.
    _RETIRED_PREFIX = "metrics:_retired:"
    _RETIRED_ACCUM_KEY = "metrics:_retired:_accum"
    METRICS_TTL_S = 600.0
    METRICS_RETIRE_FOLD_S = 86400.0

    def _metrics_kv_put(self, key: str, payload: bytes) -> None:
        """Loop-thread insert of a live metrics snapshot: stamps the
        last-write time (the TTL scan reads this instead of unpickling
        every snapshot every round) and supersedes any parked retired
        copy from the same origin."""
        self.kv[key] = payload
        self._metrics_seen[key] = time.time()
        rkey = self._RETIRED_PREFIX + key[len("metrics:"):]
        if self.kv.pop(rkey, None) is not None:
            self._metrics_seen.pop(rkey, None)

    async def _metrics_loop(self) -> None:
        import pickle as _pickle

        from ray_tpu.util import metrics as um

        g_nodes = um.get_gauge("ray_tpu_nodes_alive",
                               "Nodes currently registered and alive")
        g_actors = um.get_gauge("ray_tpu_actors_alive",
                                "Actors currently in the ALIVE state")
        g_tasks = um.get_gauge(
            "ray_tpu_task_events_stored",
            "Task events retained in the GCS ring buffer")
        while True:
            try:
                await asyncio.sleep(2.0)
                g_nodes.set(sum(1 for n in self.nodes.values() if n.alive))
                g_actors.set(sum(1 for a in self.actors.values()
                                 if a.state == "ALIVE"))
                g_tasks.set(float(len(self.task_events)))
                now = time.time()
                for key in [k for k in self.kv
                            if k.startswith("metrics:")
                            and not k.startswith(self._RETIRED_PREFIX)]:
                    seen = self._metrics_seen.get(key)
                    if seen is None:
                        # First sighting (e.g. written before this loop
                        # started): grace period begins now.
                        self._metrics_seen[key] = now
                        continue
                    if now - seen <= self.METRICS_TTL_S:
                        continue
                    try:
                        snaps = [s for s in _pickle.loads(bytes(self.kv[key]))
                                 if s.get("kind") in ("counter", "histogram")]
                    except Exception:
                        # Not a telemetry snapshot (foreign data under the
                        # metrics: prefix): never delete what we can't read
                        # — and re-stamp so we only retry once per TTL, not
                        # every 2s round.
                        self._metrics_seen[key] = now
                        continue
                    self.kv.pop(key, None)
                    self._metrics_seen.pop(key, None)
                    if snaps:
                        rkey = self._RETIRED_PREFIX + key[len("metrics:"):]
                        self.kv[rkey] = _pickle.dumps(snaps, protocol=5)
                        self._metrics_seen[rkey] = now
                # Fold long-retired parked copies into the accumulator.
                expired: List[Dict[str, Any]] = []
                for key in [k for k in self.kv
                            if k.startswith(self._RETIRED_PREFIX)
                            and k != self._RETIRED_ACCUM_KEY]:
                    seen = self._metrics_seen.setdefault(key, now)
                    if now - seen <= self.METRICS_RETIRE_FOLD_S:
                        continue
                    try:
                        expired.extend(_pickle.loads(bytes(self.kv[key])))
                    except Exception:
                        pass
                    self.kv.pop(key, None)
                    self._metrics_seen.pop(key, None)
                if expired:
                    merged: Dict[str, Any] = {}
                    fresh: Dict[Any, float] = {}
                    cur = self.kv.get(self._RETIRED_ACCUM_KEY)
                    if cur:
                        um.merge_snapshot(merged, fresh,
                                          _pickle.loads(bytes(cur)))
                    um.merge_snapshot(merged, fresh, expired)
                    self.kv[self._RETIRED_ACCUM_KEY] = _pickle.dumps(
                        [{"name": name, "kind": m["kind"],
                          "description": m["description"],
                          "values": m["values"], "ts": now}
                         for name, m in merged.items()], protocol=5)
            except asyncio.CancelledError:
                return
            except Exception:
                pass  # telemetry must never hurt the control plane

    async def stop(self) -> None:
        for t in self._background:
            t.cancel()
        for c in self._nodelet_clients.values():
            await c.close()
        if self.storage.path and self.storage.dirty:
            try:
                self.storage.save(self._snapshot_tables())
            except Exception:
                pass
        await self.server.stop()

    def _nodelet(self, node_id: NodeID) -> RpcClient:
        if node_id not in self._nodelet_clients:
            info = self.nodes[node_id]
            self._nodelet_clients[node_id] = RpcClient(*info.address, name="nodelet")
        return self._nodelet_clients[node_id]

    # ------------------------------------------------------------------
    # Node management (reference: gcs_node_manager.h:49)
    # ------------------------------------------------------------------
    async def rpc_register_node(
        self, node_id: bytes, address: Tuple[str, int],
        resources: Dict[str, float], object_store_path: str,
        labels: Optional[Dict[str, str]] = None,
    ) -> Dict[str, Any]:
        nid = NodeID(node_id)
        self.nodes[nid] = NodeInfo(nid, tuple(address), resources,
                                   object_store_path, labels or {})
        await self.pubsub.publish("nodes", {"event": "added", "node_id": node_id,
                                            "address": address})
        self._export_event("EXPORT_NODE", {
            "node_id": nid.hex(), "state": "ALIVE",
            "resources": resources, "labels": labels or {}})
        logger.info("node %s registered: %s", nid, resources)
        return {"ok": True}

    async def rpc_heartbeat(
        self, node_id: bytes, resources_available: Dict[str, float],
        load: Optional[Dict[str, Any]] = None,
        demand: Optional[List[Dict[str, float]]] = None,
        version: int = 0,
    ) -> Dict[str, Any]:
        nid = NodeID(node_id)
        info = self.nodes.get(nid)
        if info is None or not info.alive:
            # Unknown OR previously declared dead (e.g. a transient stall
            # exceeded the failure threshold): the node must re-register to
            # rejoin scheduling — its actors were already failed over.
            return {"ok": False, "reregister": True}
        info.last_heartbeat = time.monotonic()
        self._apply_resource_view(info, version, resources_available,
                                  demand or [])
        return {"ok": True}

    @staticmethod
    def _apply_resource_view(info, version: int,
                             resources_available: Dict[str, float],
                             demand: List[Dict[str, float]]) -> None:
        """Versioned apply (reference: ray_syncer's versioned snapshots,
        ray_syncer.h:40): an out-of-order sync or a heartbeat racing a
        fresher push must never roll the view back."""
        current = getattr(info, "resource_version", 0)
        if version < current:
            return
        info.resource_version = version
        info.resources_available = resources_available
        info.demand = demand

    async def rpc_sync_resources(
        self, node_id: bytes, version: int,
        resources_available: Dict[str, float],
        demand: Optional[List[Dict[str, float]]] = None,
    ) -> Dict[str, Any]:
        """Event-driven resource-view push (the ray_syncer analog): sent
        by nodelets within ~50 ms of an availability/demand change, so
        scheduling and autoscaling views are bounded by the debounce, not
        the heartbeat period."""
        info = self.nodes.get(NodeID(node_id))
        if info is None or not info.alive:
            return {"ok": False, "reregister": True}
        self._apply_resource_view(info, version, resources_available,
                                  demand or [])
        return {"ok": True}

    async def rpc_list_nodes(self) -> List[Dict[str, Any]]:
        return [
            {
                "node_id": n.node_id.binary(),
                "address": n.address,
                "alive": n.alive,
                "resources_total": n.resources_total,
                "resources_available": n.resources_available,
                "object_store_path": n.object_store_path,
                "labels": n.labels,
                "demand": n.demand,
            }
            for n in self.nodes.values()
        ]

    async def rpc_drain_node(self, node_id: bytes) -> Dict[str, Any]:
        nid = NodeID(node_id)
        info = self.nodes.get(nid)
        if info is None:
            return {"ok": False}
        await self._mark_node_dead(info, "drained")
        return {"ok": True}

    async def _health_check_loop(self) -> None:
        cfg = get_config()
        last = time.monotonic()
        while True:
            await asyncio.sleep(cfg.heartbeat_interval_s)
            deadline = cfg.heartbeat_interval_s * cfg.heartbeat_failure_threshold
            now = time.monotonic()
            # How late this loop itself woke. While the GCS was not running
            # (the whole host stalled — a co-located TPU worker compiling
            # or initializing the runtime can freeze it for seconds) it
            # could not have heard a beat, and the beats sent meanwhile are
            # queued behind this callback: that silence is no evidence.
            stall = now - last - cfg.heartbeat_interval_s
            last = now
            if stall > cfg.heartbeat_interval_s:
                logger.warning("health check woke %.1fs late; not counting "
                               "that silence against any node", stall)
                for info in self.nodes.values():
                    info.last_heartbeat += stall
            for info in list(self.nodes.values()):
                if info.alive and now - info.last_heartbeat > deadline:
                    await self._mark_node_dead(info, "heartbeat timeout")

    async def _mark_node_dead(self, info: NodeInfo, reason: str) -> None:
        info.alive = False
        self._export_event("EXPORT_NODE", {
            "node_id": info.node_id.hex(), "state": "DEAD",
            "reason": reason})
        logger.warning("node %s dead: %s", info.node_id, reason)
        await self.pubsub.publish(
            "nodes", {"event": "removed", "node_id": info.node_id.binary(),
                      "reason": reason})
        # Fail over actors that lived on that node.
        for actor in list(self.actors.values()):
            if actor.node_id == info.node_id and actor.state == ACTOR_ALIVE:
                await self._on_actor_worker_death(actor, f"node died: {reason}")

    # ------------------------------------------------------------------
    # Internal KV + function store (reference: gcs_kv_manager.h,
    # gcs_function_manager.h)
    # ------------------------------------------------------------------
    async def rpc_kv_put(self, key: str, value: bytes,
                         overwrite: bool = True) -> bool:
        """Returns True iff the key already existed (write is skipped when
        overwrite=False), so first-writer-wins checks are a single RPC."""
        existed = key in self.kv
        if existed and not overwrite:
            return True
        # metrics:* snapshots arrive every ~2s from every process and are
        # excluded from the persisted snapshot — marking dirty for them
        # would rewrite an unchanged store to disk forever on idle clusters.
        if key.startswith("metrics:"):
            self._metrics_kv_put(key, value)
        else:
            self.kv[key] = value
            self.mark_dirty()
        return existed

    async def rpc_kv_cas(self, key: str, expect: Optional[bytes],
                         value: bytes) -> bool:
        """Atomic compare-and-swap (the GCS event loop serializes RPCs):
        writes `value` iff the current value is exactly `expect`
        (None = key absent). Lease-style leader claims build on this."""
        if self.kv.get(key) != expect:
            return False
        self.kv[key] = value
        self.mark_dirty()
        return True

    async def rpc_kv_get(self, key: str) -> Optional[bytes]:
        return self.kv.get(key)

    async def rpc_kv_del(self, key: str) -> bool:
        return self.kv.pop(key, None) is not None

    async def rpc_kv_keys(self, prefix: str = "") -> List[str]:
        return [k for k in self.kv if k.startswith(prefix)]

    # ------------------------------------------------------------------
    # Jobs (reference: gcs_job_manager.h:52)
    # ------------------------------------------------------------------
    async def rpc_add_job(self, metadata: Dict[str, Any]) -> int:
        self._job_counter += 1
        self.jobs[self._job_counter] = {
            "job_id": self._job_counter, "start_time": time.time(),
            "state": "RUNNING", **metadata,
        }
        self.mark_dirty()
        return self._job_counter

    async def rpc_finish_job(self, job_id: int) -> None:
        if job_id in self.jobs:
            self.jobs[job_id]["state"] = "FINISHED"
            self.jobs[job_id]["end_time"] = time.time()
            self._export_event("EXPORT_DRIVER_JOB", {
                "job_id": job_id, "state": "FINISHED"})
        # Non-detached actors of the job die with it.
        for actor in list(self.actors.values()):
            if (not actor.detached and actor.state != ACTOR_DEAD
                    and actor.actor_id.job_id().int() == job_id):
                await self._kill_actor(actor, "job finished", no_restart=True)

    async def rpc_list_jobs(self) -> List[Dict[str, Any]]:
        return list(self.jobs.values())

    # ------------------------------------------------------------------
    # Cluster resource view / scheduling hints (reference:
    # gcs_resource_manager.h + cluster_resource_scheduler)
    # ------------------------------------------------------------------
    def _alive_nodes(self) -> List[NodeInfo]:
        return [n for n in self.nodes.values() if n.alive]

    def _pick_node(self, resources: Dict[str, float],
                   strategy: str = "hybrid",
                   exclude: Optional[set] = None,
                   label_selector: Optional[Dict[str, str]] = None
                   ) -> Optional[NodeInfo]:
        """Composite policy (reference: composite_scheduling_policy.h:33 —
        feasibility filters then a placement score): label-selector and
        resource feasibility first (label_selector.h semantics via
        _private/labels.py), then hybrid pack-most-utilized
        (hybrid_scheduling_policy.h:50) or spread least-utilized."""
        from ray_tpu._private.labels import match_label_selector

        req = ResourceSet(resources)
        candidates = [
            n for n in self._alive_nodes()
            if (exclude is None or n.node_id not in exclude)
            and req.fits_in(n.resources_available)
            and match_label_selector(label_selector, n.labels)
        ]
        if not candidates:
            return None

        def utilization(n: NodeInfo) -> float:
            used = [
                1 - n.resources_available.get(k, 0) / v
                for k, v in n.resources_total.items() if v > 0
            ]
            return max(used) if used else 0.0

        if strategy == "spread":
            # Round-robin among the least-utilized candidates: a pure
            # utilization sort is deterministic between heartbeats, which
            # would send every pick in a burst to the same node.
            candidates.sort(key=lambda n: (utilization(n), n.node_id.hex()))
            self._spread_rr += 1
            return candidates[self._spread_rr % len(candidates)]
        return sorted(candidates, key=lambda n: (utilization(n), n.node_id.hex()),
                      reverse=True)[0]

    async def rpc_pick_node(
        self, resources: Dict[str, float], strategy: str = "hybrid",
        exclude: Optional[List[bytes]] = None,
        label_selector: Optional[Dict[str, str]] = None,
    ) -> Optional[Dict[str, Any]]:
        node = self._pick_node(
            resources, strategy,
            {NodeID(e) for e in exclude} if exclude else None,
            label_selector=label_selector)
        if node is None:
            return None
        return {"node_id": node.node_id.binary(), "address": node.address,
                "object_store_path": node.object_store_path}

    # ------------------------------------------------------------------
    # Actor management (reference: gcs_actor_manager.h:331 — the FSM)
    # ------------------------------------------------------------------
    def _actor_lock(self, actor_id: ActorID) -> asyncio.Lock:
        return self._actor_locks.setdefault(actor_id, asyncio.Lock())

    async def rpc_register_actor(
        self, actor_id: bytes, creation_spec: bytes, name: str = "",
        max_restarts: int = 0, detached: bool = False,
        get_if_exists: bool = False,
    ) -> Dict[str, Any]:
        aid = ActorID(actor_id)
        # Idempotent: a retried registration (client call_retrying after an
        # RPC blip) must not double-schedule or steal its own name
        # (reference: gcs_actor_manager.cc RegisterActor dedup).
        if aid in self.actors:
            return {"ok": True}
        if name:
            existing = self.named_actors.get(name)
            if existing is not None and existing != aid:
                if get_if_exists:
                    # Atomic get-or-create (reference: actor.py
                    # get_if_exists option → GetOrCreate in GCS).
                    return {"ok": True,
                            "existing_actor_id": existing.binary()}
                return {"ok": False,
                        "error": f"actor name {name!r} already taken"}
            self.named_actors[name] = aid
            self.mark_dirty()
        info = ActorInfo(aid, creation_spec, name, max_restarts, detached)
        self.actors[aid] = info
        self.mark_dirty()
        if self._prekilled.pop(aid, None) is not None:
            # A kill raced ahead of this (asynchronous) registration:
            # land the actor dead instead of scheduling a zombie.
            await self._actor_dead(info, "killed before registration")
            return {"ok": True}
        asyncio.ensure_future(self._schedule_actor(info))
        return {"ok": True}

    async def _schedule_actor(self, info: ActorInfo) -> None:
        async with self._actor_lock(info.actor_id):
            await self._schedule_actor_locked(info)

    async def _schedule_actor_locked(self, info: ActorInfo) -> None:
        import pickle

        from ray_tpu._private.task_spec import (NodeAffinityStrategy,
                                                PlacementGroupStrategy,
                                                SpreadStrategy)

        spec = pickle.loads(info.creation_spec)
        cfg = get_config()
        # Unified retry policy: full-jitter backoff de-synchronizes actor
        # scheduling herds (N restarting actors after a node death).
        # One clock for the whole scheduling budget: bo paces the retries
        # AND bounds them (bo.expired() is the terminal check).
        bo = Backoff(deadline=cfg.worker_start_timeout_s)
        strategy = spec.scheduling_strategy
        while info.state in (ACTOR_PENDING, ACTOR_RESTARTING):
            pg_bundle = None
            if isinstance(strategy, PlacementGroupStrategy):
                pgid = PlacementGroupID(strategy.placement_group_id)
                pg = self.placement_groups.get(pgid)
                bundle_idx = max(strategy.bundle_index, 0)
                nid = (pg.bundle_nodes.get(bundle_idx)
                       if pg is not None and pg.state == "CREATED" else None)
                node = self.nodes.get(nid) if nid is not None else None
                if node is not None and not node.alive:
                    node = None
                pg_bundle = (strategy.placement_group_id, bundle_idx)
            elif isinstance(strategy, NodeAffinityStrategy):
                nid = NodeID(bytes.fromhex(strategy.node_id))
                node = self.nodes.get(nid)
                if node is not None and not node.alive:
                    node = None
                if node is None and strategy.soft:
                    node = self._pick_node(spec.resources)
            elif isinstance(strategy, SpreadStrategy):
                node = self._pick_node(
                    spec.resources, strategy="spread",
                    label_selector=getattr(spec, "label_selector", None))
            else:
                node = self._pick_node(
                    spec.resources,
                    label_selector=getattr(spec, "label_selector", None))
            if node is None:
                if not await bo.sleep():
                    await self._actor_dead(
                        info, "no node with required resources "
                        f"{dict(spec.resources)}")
                    return
                continue
            try:
                lease = await self._nodelet(node.node_id).call(
                    "lease_worker",
                    resources=dict(spec.resources),
                    runtime_env=spec.runtime_env,
                    lifetime="actor",
                    pg_bundle=pg_bundle,
                    timeout=cfg.worker_start_timeout_s,
                )
                if not lease.get("ok"):
                    # Resources busy on the picked node: the actor stays
                    # pending (another lease may free them). Once the
                    # backoff deadline is exhausted sleep() returns False
                    # WITHOUT sleeping — keep pacing at the jittered cap
                    # (never in lockstep) instead of hot-spinning leases.
                    if not await bo.sleep():
                        await asyncio.sleep(
                            delay_for_attempt(64, maximum=bo.maximum))
                    continue
                worker_addr = tuple(lease["worker_address"])
                worker_client = RpcClient(*worker_addr, name="actor-worker")
                result = await worker_client.call(
                    "create_actor", creation_spec=info.creation_spec,
                    timeout=cfg.worker_start_timeout_s)
                await worker_client.close()
                if not result.get("ok"):
                    await self._actor_dead(
                        info, f"creation failed: {result.get('error')}")
                    return
                info.state = ACTOR_ALIVE
                self._export_event("EXPORT_ACTOR", {
                    "actor_id": info.actor_id.hex(), "state": "ALIVE",
                    "name": info.name,
                    "node_id": info.node_id.hex() if info.node_id
                    else None})
                self.mark_dirty()
                info.address = worker_addr
                info.node_id = node.node_id
                await self.pubsub.publish(
                    "actors", {"event": "alive",
                               "actor": info.public_view()})
                logger.info("actor %s alive at %s", info.actor_id, worker_addr)
                return
            except Exception as e:
                logger.warning("actor %s scheduling attempt failed: %r",
                               info.actor_id, e)
                if not await bo.sleep():
                    await self._actor_dead(info, f"scheduling failed: {e!r}")
                    return

    async def _actor_dead(self, info: ActorInfo, cause: str) -> None:
        info.state = ACTOR_DEAD
        self._export_event("EXPORT_ACTOR", {
            "actor_id": info.actor_id.hex(), "state": "DEAD",
            "name": info.name, "death_cause": cause})
        self.mark_dirty()
        info.death_cause = cause
        info.address = None
        if info.name:
            self.named_actors.pop(info.name, None)
        await self.pubsub.publish(
            "actors", {"event": "dead", "actor": info.public_view()})
        logger.info("actor %s dead: %s", info.actor_id, cause)

    async def _on_actor_worker_death(self, info: ActorInfo, cause: str) -> None:
        """FSM transition on worker failure (reference:
        gcs_actor_manager.cc:1318 RestartActor)."""
        async with self._actor_lock(info.actor_id):
            if info.state == ACTOR_DEAD:
                return
            if info.max_restarts == -1 or info.num_restarts < info.max_restarts:
                info.num_restarts += 1
                info.state = ACTOR_RESTARTING
                self.mark_dirty()
                info.address = None
                await self.pubsub.publish(
                    "actors", {"event": "restarting",
                               "actor": info.public_view()})
                logger.info("restarting actor %s (%d)", info.actor_id,
                            info.num_restarts)
                await self._schedule_actor_locked(info)
            else:
                await self._actor_dead(info, cause)

    async def rpc_report_worker_death(
        self, node_id: bytes, worker_address: Tuple[str, int], reason: str,
        actor_ids: Optional[List[bytes]] = None,
    ) -> None:
        addr = tuple(worker_address)
        for info in list(self.actors.values()):
            if info.state == ACTOR_ALIVE and info.address == addr:
                asyncio.ensure_future(
                    self._on_actor_worker_death(info, f"worker died: {reason}"))

    async def rpc_get_actor(self, actor_id: bytes) -> Optional[Dict[str, Any]]:
        info = self.actors.get(ActorID(actor_id))
        return info.public_view() if info else None

    async def rpc_get_named_actor(self, name: str) -> Optional[Dict[str, Any]]:
        aid = self.named_actors.get(name)
        if aid is None:
            return None
        return self.actors[aid].public_view()

    async def rpc_list_actors(self) -> List[Dict[str, Any]]:
        return [a.public_view() for a in self.actors.values()]

    # Tombstones older than this can't belong to an in-flight registration
    # (the register pipeline is bounded by worker_start_timeout_s + RPC
    # retries); the cap is a backstop against kill floods of bogus ids.
    PREKILL_TTL_S = 300.0
    PREKILL_MAX = 4096

    async def rpc_kill_actor(self, actor_id: bytes,
                             no_restart: bool = True) -> Dict[str, Any]:
        info = self.actors.get(ActorID(actor_id))
        if info is None:
            # Actor registration is asynchronous on the client: a kill can
            # legitimately arrive BEFORE register_actor. Tombstone the id
            # so the late registration lands dead instead of leaking a
            # zombie nobody holds a handle to.
            now = time.monotonic()
            self._prekilled.pop(ActorID(actor_id), None)  # refresh order
            self._prekilled[ActorID(actor_id)] = now
            for aid, ts in list(self._prekilled.items()):
                if (now - ts <= self.PREKILL_TTL_S
                        and len(self._prekilled) <= self.PREKILL_MAX):
                    break
                del self._prekilled[aid]
            return {"ok": False, "error": "no such actor"}
        # Reply as soon as the kill is ACCEPTED (reference: ray.kill is
        # asynchronous); the FSM transition + worker exit proceed on this
        # loop. A churn wave killing N actors then pays N cheap acks, not
        # N full teardowns.
        asyncio.ensure_future(
            self._kill_actor(info, "ray_tpu.kill", no_restart=no_restart))
        return {"ok": True}

    async def _kill_actor(self, info: ActorInfo, cause: str,
                          no_restart: bool) -> None:
        addr = info.address
        if no_restart:
            await self._actor_dead(info, cause)
        if addr is not None:
            try:
                client = RpcClient(*addr, name="kill")
                await client.call("exit_worker", timeout=5)
                await client.close()
            except Exception:
                pass  # worker may already be gone; nodelet reaps it

    # ------------------------------------------------------------------
    # Placement groups (reference: gcs_placement_group_mgr.h:232; 2-phase
    # prepare/commit via nodelets, bundle policies C15/C17)
    # ------------------------------------------------------------------
    async def rpc_create_placement_group(
        self, pg_id: bytes, bundles: List[Dict[str, float]], strategy: str,
        name: str = "",
    ) -> Dict[str, Any]:
        pgid = PlacementGroupID(pg_id)
        info = PlacementGroupInfo(pgid, bundles, strategy, name)
        self.placement_groups[pgid] = info
        self.mark_dirty()
        ok = await self._schedule_pg(info)
        if ok:
            info.state = "CREATED"
            self._export_event("EXPORT_PLACEMENT_GROUP", {
                "pg_id": info.pg_id.hex(), "state": "CREATED",
                "strategy": info.strategy})
            self.mark_dirty()
            await self.pubsub.publish("placement_groups",
                                      {"event": "created", "pg_id": pg_id})
            return {"ok": True,
                    "bundle_nodes": {i: nid.binary()
                                     for i, nid in info.bundle_nodes.items()}}
        # Stay PENDING: the retry loop re-schedules as the resource view
        # refreshes / nodes join (reference: GcsPlacementGroupManager retry
        # queue). Permanent infeasibility is indistinguishable from "not yet".
        return {"ok": False, "error": "placement group pending", "retry": True}

    async def _pg_retry_loop(self) -> None:
        while True:
            await asyncio.sleep(0.5)
            for info in list(self.placement_groups.values()):
                if info.state != "PENDING":
                    continue
                try:
                    # _schedule_pg itself handles the removed-while-
                    # scheduling race (membership check + bundle return).
                    if await self._schedule_pg(info):
                        info.state = "CREATED"
                        self._export_event("EXPORT_PLACEMENT_GROUP", {
                            "pg_id": info.pg_id.hex(), "state": "CREATED",
                            "strategy": info.strategy})
                        self.mark_dirty()
                        await self.pubsub.publish(
                            "placement_groups",
                            {"event": "created",
                             "pg_id": info.pg_id.binary()})
                except Exception as e:
                    logger.warning("pg retry failed: %r", e)

    async def _schedule_pg(self, info: PlacementGroupInfo) -> bool:
        # Choose nodes per bundle under the strategy.
        sim_avail = {
            n.node_id: dict(n.resources_available) for n in self._alive_nodes()
        }
        assignment: Dict[int, NodeID] = {}
        used_nodes: set = set()
        for i, bundle in enumerate(info.bundles):
            req = ResourceSet(bundle)
            candidates = [
                nid for nid, avail in sim_avail.items() if req.fits_in(avail)
            ]
            if info.strategy in ("STRICT_PACK", "PACK") and assignment:
                pref = [nid for nid in candidates if nid in used_nodes]
                if pref:
                    candidates = pref
                elif info.strategy == "STRICT_PACK":
                    return False
            if info.strategy == "STRICT_SPREAD":
                candidates = [nid for nid in candidates if nid not in used_nodes]
            elif info.strategy == "SPREAD":
                fresh = [nid for nid in candidates if nid not in used_nodes]
                if fresh:
                    candidates = fresh
            if not candidates:
                return False
            nid = candidates[0]
            req.subtract_from(sim_avail[nid])
            assignment[i] = nid
            used_nodes.add(nid)
        # 2-phase: prepare all, then commit (reference:
        # placement_group_resource_manager.h:50).
        prepared: List[Tuple[NodeID, int]] = []
        try:
            for i, nid in assignment.items():
                r = await self._nodelet(nid).call(
                    "prepare_bundle", pg_id=info.pg_id.binary(),
                    bundle_index=i, resources=info.bundles[i])
                if not r.get("ok"):
                    raise RuntimeError("prepare failed")
                prepared.append((nid, i))
            for i, nid in assignment.items():
                await self._nodelet(nid).call(
                    "commit_bundle", pg_id=info.pg_id.binary(), bundle_index=i)
        except Exception as e:
            logger.warning("pg %s scheduling failed: %r", info.pg_id, e)
            for nid, i in prepared:
                try:
                    await self._nodelet(nid).call(
                        "return_bundle", pg_id=info.pg_id.binary(),
                        bundle_index=i)
                except Exception:
                    pass
            return False
        info.bundle_nodes = assignment
        if self.placement_groups.get(info.pg_id) is not info:
            # Removed while we were preparing/committing (the retry loop
            # races rpc_remove_placement_group): give the bundles back
            # immediately or they leak on the nodelets forever.
            for i, nid in assignment.items():
                try:
                    await self._nodelet(nid).call(
                        "return_bundle", pg_id=info.pg_id.binary(),
                        bundle_index=i)
                except Exception:
                    pass
            return False
        return True

    async def rpc_remove_placement_group(self, pg_id: bytes) -> Dict[str, Any]:
        pgid = PlacementGroupID(pg_id)
        info = self.placement_groups.pop(pgid, None)
        if info is None:
            return {"ok": False}
        info.state = "REMOVED"  # in-flight retry scheduling must not revive it
        self._export_event("EXPORT_PLACEMENT_GROUP", {
            "pg_id": info.pg_id.hex(), "state": "REMOVED"})
        self.mark_dirty()
        for i, nid in info.bundle_nodes.items():
            try:
                await self._nodelet(nid).call(
                    "return_bundle", pg_id=pg_id, bundle_index=i)
            except Exception:
                pass
        return {"ok": True}

    async def rpc_get_placement_group(self, pg_id: bytes) -> Optional[Dict[str, Any]]:
        info = self.placement_groups.get(PlacementGroupID(pg_id))
        if info is None:
            return None
        return {"pg_id": pg_id, "state": info.state, "strategy": info.strategy,
                "bundles": info.bundles,
                "bundle_nodes": {i: n.binary()
                                 for i, n in info.bundle_nodes.items()}}

    async def rpc_list_placement_groups(self) -> List[Dict[str, Any]]:
        return [
            {"pg_id": p.pg_id.binary(), "state": p.state, "name": p.name,
             "strategy": p.strategy, "bundles": p.bundles}
            for p in self.placement_groups.values()
        ]

    # ------------------------------------------------------------------
    # Pub/sub RPC surface
    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # Task events (reference: gcs_task_manager.h:94 — bounded aggregation
    # feeding the state API and `timeline`)
    # ------------------------------------------------------------------
    async def rpc_report_task_events(
            self, events: List[Dict[str, Any]]) -> None:
        self.task_events.extend(events)
        if self.export is not None:
            try:
                self.export.emit_many("EXPORT_TASK", events)
            except Exception:  # noqa: BLE001
                pass  # export is observability, never control flow

    async def rpc_list_task_events(
            self, limit: int = 1000) -> List[Dict[str, Any]]:
        return list(self.task_events)[-limit:]

    async def rpc_pubsub_poll(
        self, cursors: Dict[str, int], timeout: float = 30.0
    ) -> Dict[str, List[Tuple[int, Any]]]:
        return await self.pubsub.poll(cursors, timeout)

    async def rpc_publish(self, channel: str, message: Any) -> None:
        await self.pubsub.publish(channel, message)

    async def rpc_pubsub_seq(self, channel: str) -> int:
        """Current sequence number of a channel — lets a new subscriber
        start from "now" instead of replaying the retained backlog."""
        return self.pubsub._seq.get(channel, 0)

    async def rpc_ping(self) -> str:
        return "pong"


async def run_gcs_server(host: str, port: int,
                         persist_path: Optional[str] = None) -> GcsServer:
    gcs = GcsServer(host, port, persist_path=persist_path)
    await gcs.start()
    return gcs


def main() -> None:  # pragma: no cover - exercised via subprocess
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--persist-path", default=None)
    args = parser.parse_args()

    async def _run():
        await run_gcs_server(args.host, args.port,
                             persist_path=args.persist_path)
        await asyncio.Event().wait()

    asyncio.run(_run())


if __name__ == "__main__":
    main()
