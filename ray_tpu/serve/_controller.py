"""Serve controller actor (reference: serve/_private/controller.py:92 +
deployment_state.py:1379 reconciler + autoscaling_state.py).

Redesign: one actor holds the desired state (deployment configs) and
reconciles actual replica actors toward it in a background thread. Methods
are sync — they run on the actor's executor threads, where blocking
runtime calls (actor creation, gets) are legal; an async controller would
deadlock creating replicas from its own event loop. Instead of the
reference's long-poll host, consumers poll `get_routing(version)` — the
version check makes the poll cheap, and handle-side caching makes it rare.

Closed-loop autoscaling (this file orchestrates; policy lives in
`_autoscaling.py`):

* The controller never polls replicas for load. Replicas PUSH
  ``{ongoing, shed_delta}`` via ``report_replica_load`` on their own
  heartbeat cadence, and the same numbers piggyback on ``check_health``
  replies as the poll-based fallback. Handles and proxies piggyback
  ``{queued, shed_delta}`` on the routing calls they already make
  (``wait_routing`` / ``get_routing``), so the signal plane adds zero new
  RPC streams.
* Health checks fan out in parallel (fire all refs, then collect) — the
  old serial loop meant one wedged replica delayed every other
  deployment's health verdict by its full timeout.
* Scale-down drains run on background threads so a replica dying
  mid-``prepare_for_shutdown`` can never wedge the reconcile cadence;
  explicit teardown (delete_deployment/shutdown_all) stays synchronous.
* Replica boots that fail back off exponentially per deployment
  (``_private/backoff.py``) instead of hot-spinning a crash loop.
* Desired state + autoscaler windows are checkpointed to the GCS
  internal KV and replicas are NAMED actors, so a controller restarted
  mid-scale re-adopts the live replica set and resumes the same decision
  windows instead of resetting (and leaking the old actors).
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

import ray_tpu
from ray_tpu.serve._autoscaling import (
    DeploymentAutoscaler,
    pick_scale_down_victims,
    resolve_config,
)
from ray_tpu.serve._common import DeploymentConfig, ReplicaInfo
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# GCS internal-KV key holding the controller checkpoint.
CHECKPOINT_KEY = b"serve:controller_ckpt"
# Replica actors are named so a restarted controller can re-adopt the
# live set from its checkpoint instead of leaking them.
REPLICA_NAME_PREFIX = "SERVE_REPLICA::"
# A replica still booting (worker spawn + model load) gets this long
# before an unhealthy check means "replace".
STARTUP_GRACE_S = 180.0
# A booted replica that does not answer a health check in time (10 s) is
# taken out of routing at once and replaced only when this many checks in a
# row time out (some 30 s of silence; the reference's default health check
# timeout is 30 s): its process may hold the interpreter for seconds at a
# stretch (reducing a profiler trace of 800k events took 11 s; my chip run,
# PR 37) and answer again. A dead actor, or a check that raises, is replaced
# at the first.
HEALTH_TIMEOUTS_TO_REPLACE = 3


class ServeController:
    def __init__(self):
        # name -> {config, ctor, args, kwargs, base_replicas}
        self._deployments: Dict[str, Dict[str, Any]] = {}
        self._replicas: Dict[str, List[ReplicaInfo]] = {}
        self._version = 0
        self._running = False
        self._http_port: Optional[int] = None
        self._autoscalers: Dict[str, DeploymentAutoscaler] = {}
        # name -> {"attempt": int, "until": monotonic} replica-boot backoff.
        self._boot_backoff: Dict[str, Dict[str, float]] = {}
        self._ckpt_dirty = False
        self._lock = threading.RLock()
        from ray_tpu.util import metrics as um

        # Registered up front (not at first decision) so the name is in
        # the /metrics exposition from boot — dashboards and the
        # metrics-contract live test see it before any scaling happens.
        self._m_decisions = um.get_counter(
            "ray_tpu_serve_autoscale_decisions_total",
            "Applied serve autoscaling decisions",
            tag_keys=("deployment", "direction", "reason"))

    def start_loops(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
        try:
            self._restore_from_checkpoint()
        except Exception:
            logger.exception("checkpoint restore failed; starting fresh")
        threading.Thread(target=self._reconcile_thread, daemon=True,
                         name="serve-reconcile").start()

    # ------------------------------------------------------------------
    # Deploy API
    # ------------------------------------------------------------------
    def deploy(self, name: str, serialized_ctor: bytes,
               init_args: Tuple, init_kwargs: Dict,
               config: Dict[str, Any]) -> None:
        with self._lock:
            cfg = DeploymentConfig(name=name, **config)
            cfg.version = self._version + 1
            self._deployments[name] = {
                "config": cfg,
                "ctor": serialized_ctor,
                "args": init_args,
                "kwargs": init_kwargs,
                # The CONFIGURED count, before any autoscale decision
                # mutates cfg.num_replicas — autoscaling_config without an
                # explicit max_replicas clamps here, so decisions can
                # never ratchet the ceiling up by raising their own
                # fallback.
                "base_replicas": cfg.num_replicas,
            }
            self._autoscalers.setdefault(name, DeploymentAutoscaler())
            self._boot_backoff.pop(name, None)
            self._version += 1
        self._save_checkpoint()
        self._reconcile_once()

    def delete_deployment(self, name: str) -> None:
        with self._lock:
            d = self._deployments.pop(name, None)
            victims = self._replicas.pop(name, [])
            self._autoscalers.pop(name, None)
            self._boot_backoff.pop(name, None)
            self._version += 1
        self._save_checkpoint()
        grace = (d["config"].graceful_shutdown_timeout_s if d else 5.0)
        self._drain_and_kill(victims, grace)

    def shutdown_all(self) -> None:
        with self._lock:
            self._running = False
            names = list(self._deployments)
        for name in names:
            self.delete_deployment(name)
        try:
            from ray_tpu.experimental.internal_kv import _internal_kv_del

            _internal_kv_del(CHECKPOINT_KEY)
        except Exception:
            pass

    # ------------------------------------------------------------------
    # Load-report intake (the autoscaling signal plane)
    # ------------------------------------------------------------------
    def report_replica_load(self, name: str, replica_id: str,
                            ongoing: int, shed_delta: float = 0.0) -> None:
        """Push path: each replica's heartbeat thread calls this every
        ~0.5s. Cheap on purpose — record under the lock, no decisions."""
        with self._lock:
            a = self._autoscalers.get(name)
            if a is not None:
                a.record_replica(replica_id, ongoing, shed_delta,
                                 time.time())

    def _ingest_ingress_report(self, load_report: Optional[Dict[str, Any]]
                               ) -> None:
        """Piggybacked handle/proxy report:
        ``{"reporter": id, "deployments": {name: {queued, shed_delta}}}``."""
        if not load_report:
            return
        reporter = str(load_report.get("reporter", "?"))
        now = time.time()
        with self._lock:
            for name, rep in (load_report.get("deployments") or {}).items():
                a = self._autoscalers.get(name)
                if a is not None:
                    a.record_ingress(reporter,
                                     int(rep.get("queued", 0) or 0),
                                     float(rep.get("shed_delta", 0) or 0),
                                     now)

    def get_autoscale_state(self, name: str) -> Optional[Dict[str, Any]]:
        """Introspection for tests/debugging: the deployment's current
        autoscaler window state plus the live target."""
        with self._lock:
            a = self._autoscalers.get(name)
            d = self._deployments.get(name)
            if a is None or d is None:
                return None
            state = a.to_state()
            state["target_num_replicas"] = d["config"].num_replicas
            state["running"] = len(self._replicas.get(name, []))
            return state

    # ------------------------------------------------------------------
    # Discovery (handles + proxy)
    # ------------------------------------------------------------------
    async def wait_routing(self, known_version: int = -1,
                           timeout: float = 30.0,
                           load_report: Optional[Dict[str, Any]] = None
                           ) -> Optional[Dict[str, Any]]:
        """Long-poll: return the routing table once it is NEWER than
        known_version, or None at timeout (reference:
        serve/_private/long_poll.py:222 LongPollHost.listen_for_change).
        Async so parked polls ride the actor's event loop instead of
        pinning executor threads — one outstanding call per handle.
        ``load_report`` piggybacks the handle's queue depth + shed delta;
        ingested at ENTRY, before the poll parks, so the signal is at most
        one poll period old, not one poll WINDOW old."""
        import asyncio

        self._ingest_ingress_report(load_report)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            routing = self.get_routing(known_version)
            if routing is not None:
                return routing
            await asyncio.sleep(0.05)
        return None

    def get_routing(self, known_version: int = -1,
                    load_report: Optional[Dict[str, Any]] = None
                    ) -> Optional[Dict[str, Any]]:
        """Replica handles + route prefixes, or None when unchanged."""
        self._ingest_ingress_report(load_report)
        with self._lock:
            if known_version == self._version:
                return None
            return {
                "version": self._version,
                "deployments": {
                    name: {
                        "replicas": [(i.replica_id, i.actor)
                                     for i in self._replicas.get(name, [])
                                     if i.healthy],
                        "route_prefix": d["config"].route_prefix,
                        "max_ongoing_requests":
                            d["config"].max_ongoing_requests,
                        "max_queued_requests":
                            d["config"].max_queued_requests,
                        "request_timeout_s":
                            d["config"].request_timeout_s,
                        "request_router": d["config"].request_router,
                    }
                    for name, d in self._deployments.items()
                },
            }

    def get_status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                name: {
                    "target": d["config"].num_replicas,
                    "running": sum(1 for i in self._replicas.get(name, [])
                                   if i.healthy),
                    "version": d["config"].version,
                }
                for name, d in self._deployments.items()
            }

    def set_http_port(self, port: int) -> None:
        self._http_port = port
        self._ckpt_dirty = True

    def get_http_port(self) -> Optional[int]:
        return self._http_port

    def set_grpc_port(self, port: int) -> None:
        self._grpc_port = port
        self._ckpt_dirty = True

    def get_grpc_port(self) -> Optional[int]:
        return getattr(self, "_grpc_port", None)

    # ------------------------------------------------------------------
    # Checkpoint / restore (controller restart mid-scale must RESUME)
    # ------------------------------------------------------------------
    def _save_checkpoint(self) -> None:
        import cloudpickle

        with self._lock:
            state = {
                "version": self._version,
                "deployments": {
                    name: {
                        "config": d["config"],
                        "ctor": d["ctor"],
                        "args": d["args"],
                        "kwargs": d["kwargs"],
                        "base_replicas": d.get(
                            "base_replicas", d["config"].num_replicas),
                    }
                    for name, d in self._deployments.items()
                },
                "replica_ids": {
                    name: [i.replica_id for i in infos]
                    for name, infos in self._replicas.items()
                },
                "autoscalers": {name: a.to_state()
                                for name, a in self._autoscalers.items()},
                "http_port": self._http_port,
                "grpc_port": getattr(self, "_grpc_port", None),
            }
            self._ckpt_dirty = False
        try:
            from ray_tpu.experimental.internal_kv import _internal_kv_put

            _internal_kv_put(CHECKPOINT_KEY, cloudpickle.dumps(state))
        except Exception:
            logger.exception("controller checkpoint write failed")

    def _restore_from_checkpoint(self) -> bool:
        import cloudpickle

        from ray_tpu.experimental.internal_kv import _internal_kv_get

        raw = _internal_kv_get(CHECKPOINT_KEY)
        if raw is None:
            return False
        state = cloudpickle.loads(raw)
        adopted = 0
        lost = 0
        with self._lock:
            for name, d in state.get("deployments", {}).items():
                self._deployments[name] = {
                    "config": d["config"],
                    "ctor": d["ctor"],
                    "args": d["args"],
                    "kwargs": d["kwargs"],
                    "base_replicas": d.get(
                        "base_replicas", d["config"].num_replicas),
                }
            for name, st in state.get("autoscalers", {}).items():
                self._autoscalers[name] = DeploymentAutoscaler.from_state(st)
            for name in self._deployments:
                self._autoscalers.setdefault(name, DeploymentAutoscaler())
            for name, rids in state.get("replica_ids", {}).items():
                if name not in self._deployments:
                    continue
                infos = self._replicas.setdefault(name, [])
                for rid in rids:
                    # Replicas outlive the controller (no owner-kill) —
                    # re-adopt by name; a dead/absent one is simply gone
                    # and reconcile will boot a replacement.
                    try:
                        actor = ray_tpu.get_actor(REPLICA_NAME_PREFIX + rid)
                    except Exception:
                        lost += 1
                        continue
                    info = ReplicaInfo(rid, actor)
                    info.booted = True  # survived at least one lifetime
                    infos.append(info)
                    adopted += 1
            if self._http_port is None:
                self._http_port = state.get("http_port")
            if state.get("grpc_port") is not None:
                self._grpc_port = state.get("grpc_port")
            # Strictly newer than anything a handle cached from the old
            # incarnation, so every consumer refetches.
            self._version = int(state.get("version", 0)) + 1
        logger.info(
            "controller restored from checkpoint: %d deployments, "
            "%d replicas adopted, %d lost",
            len(state.get("deployments", {})), adopted, lost)
        return True

    # ------------------------------------------------------------------
    # Reconciliation
    # ------------------------------------------------------------------
    def _reconcile_thread(self) -> None:
        while True:
            with self._lock:
                if not self._running:
                    return
            try:
                self._reconcile_once(health_check=True)
            except Exception:
                logger.exception("reconcile failed")
            time.sleep(1.0)

    def _autoscale(self, name: str, d: Dict[str, Any],
                   replicas: List[ReplicaInfo]) -> None:
        """One decision tick: feed the push-report state into the policy
        and, when a decision fires, mutate the deployment's target count,
        count the decision, and checkpoint BEFORE actuation so a
        controller killed mid-scale resumes toward the same target."""
        cfg: DeploymentConfig = d["config"]
        ac = cfg.autoscaling_config
        if not ac or not replicas:
            return
        with self._lock:
            a = self._autoscalers.setdefault(name, DeploymentAutoscaler())
            decision = a.tick(
                cfg.num_replicas,
                [i.replica_id for i in replicas],
                cfg.max_ongoing_requests, ac, time.time(),
                fallback_max=d.get("base_replicas", cfg.num_replicas))
        if decision is None:
            return
        logger.info(
            "autoscaling %s: %s to %d replicas (reason=%s load=%.1f "
            "shed_rate=%.2f/s stale=%d)", name, decision.direction,
            decision.desired, decision.reason, decision.load,
            decision.shed_rate, decision.stale)
        self._m_decisions.inc(
            tags={"deployment": name, "direction": decision.direction,
                  "reason": decision.reason})
        with self._lock:
            cfg.num_replicas = decision.desired
        self._save_checkpoint()

    def _check_health_all(self, items) -> bool:
        """Parallel health sweep: fire every replica's check_health first,
        then collect — one wedged replica costs its own timeout, not a
        serial sum across the fleet. Replies piggyback
        ``{ongoing, shed_delta}``, the poll-based fallback for the
        autoscaling signal when a replica's push thread is partitioned."""
        fired = []
        for name, d in items:
            for info in list(self._replicas.get(name, [])):
                try:
                    fired.append(
                        (name, d, info, info.actor.check_health.remote()))
                except Exception as e:
                    fired.append((name, d, info, e))
        changed = False
        deadline = time.monotonic() + 10.0
        now = time.time()
        for name, d, info, ref in fired:
            cfg: DeploymentConfig = d["config"]
            was_healthy = info.healthy
            try:
                if isinstance(ref, Exception):
                    raise ref
                result = ray_tpu.get(
                    ref, timeout=max(0.5, deadline - time.monotonic()))
                if isinstance(result, dict):
                    with self._lock:
                        a = self._autoscalers.get(name)
                        if a is not None:
                            a.record_replica(
                                info.replica_id,
                                int(result.get("ongoing", 0) or 0),
                                float(result.get("shed_delta", 0) or 0),
                                now)
                info.healthy = True
                info.health_timeouts = 0
                if not getattr(info, "booted", False):
                    info.booted = True
                    self._note_boot_success(name)
                if not was_healthy:
                    changed = True  # back in routing: push the news
            except Exception as e:
                # Startup grace: a replica still waiting on worker
                # spawn + model load (ActorUnavailable / pending)
                # must not be killed and respawned in a loop —
                # that starves the deployment forever on a loaded
                # host. Only replace once it EXCEEDS the grace
                # window or is definitively dead. While in grace
                # it is marked unhealthy so routing skips it.
                from ray_tpu.exceptions import ActorDiedError, GetTimeoutError

                age = time.monotonic() - info.created_at
                dead = isinstance(e, ActorDiedError)
                silent = 0
                if isinstance(e, GetTimeoutError):
                    silent = info.health_timeouts = getattr(
                        info, "health_timeouts", 0) + 1
                if not dead and (age < STARTUP_GRACE_S or 0 < silent
                                 < HEALTH_TIMEOUTS_TO_REPLACE):
                    info.healthy = False
                    if was_healthy:
                        # Routing filters on healthy: push the
                        # change or proxies keep sending traffic.
                        changed = True
                    logger.info(
                        "replica %s of %s not ready yet "
                        "(%.0fs): %r", info.replica_id, name, age, e)
                    continue
                logger.warning(
                    "replica %s of %s unhealthy; replacing",
                    info.replica_id, name)
                if not getattr(info, "booted", False):
                    # Died without ever passing health: a boot failure.
                    # Back off before the replacement, or a broken ctor
                    # hot-spins actor churn forever.
                    self._note_boot_failure(name)
                with self._lock:
                    replicas = self._replicas.get(name, [])
                    if info in replicas:
                        replicas.remove(info)
                    # Routing must drop the victim BEFORE the drain
                    # so handles stop picking it while it finishes.
                    self._version += 1
                    self._ckpt_dirty = True
                self._begin_drain(name, [info],
                                  cfg.graceful_shutdown_timeout_s)
                changed = True
        return changed

    def _note_boot_failure(self, name: str) -> None:
        from ray_tpu._private.backoff import delay_for_attempt

        bo = self._boot_backoff.setdefault(name, {"attempt": 0, "until": 0})
        bo["attempt"] += 1
        delay = delay_for_attempt(bo["attempt"] - 1,
                                  initial=0.5, maximum=30.0)
        bo["until"] = time.monotonic() + delay
        logger.warning("replica boot for %s failed (attempt %d); "
                       "backing off %.1fs", name, bo["attempt"], delay)

    def _note_boot_success(self, name: str) -> None:
        self._boot_backoff.pop(name, None)

    def _reconcile_once(self, health_check: bool = False) -> None:
        from ray_tpu.serve._replica import ReplicaActor

        changed = False
        with self._lock:
            items = list(self._deployments.items())
        if health_check:
            changed |= self._check_health_all(items)
            for name, d in items:
                self._autoscale(name, d, self._replicas.get(name, []))
        for name, d in items:
            with self._lock:
                if name not in self._deployments:
                    continue  # deleted concurrently
            cfg: DeploymentConfig = d["config"]
            replicas = self._replicas.setdefault(name, [])
            bo = self._boot_backoff.get(name)
            while (len(replicas) < cfg.num_replicas
                   and not (bo and time.monotonic() < bo["until"])):
                rid = f"{name}#{uuid.uuid4().hex[:6]}"
                Actor = ray_tpu.remote(ReplicaActor)
                opts = dict(cfg.ray_actor_options)
                try:
                    actor = Actor.options(
                        num_cpus=opts.get("num_cpus", 1.0),
                        num_tpus=opts.get("num_tpus") or None,
                        # Named so a restarted controller can re-adopt it
                        # from the checkpoint instead of leaking it.
                        name=REPLICA_NAME_PREFIX + rid,
                        # Headroom over the admission cap: over-capacity
                        # calls must still EXECUTE (to raise
                        # BackPressureError fast) rather than park in the
                        # actor mailbox, and health / drain / load-report
                        # control calls need slots while the replica is
                        # saturated with user requests.
                        max_concurrency=max(2, cfg.max_ongoing_requests * 2),
                    ).remote(d["ctor"], tuple(d["args"]), dict(d["kwargs"]),
                             cfg.user_config, name, cfg.max_ongoing_requests,
                             rid)
                except Exception:
                    logger.exception("replica boot for %s failed", name)
                    self._note_boot_failure(name)
                    bo = self._boot_backoff.get(name)
                    changed = True
                    continue
                with self._lock:
                    replicas.append(ReplicaInfo(rid, actor))
                    self._ckpt_dirty = True
                changed = True
                logger.info("started replica %s for %s", rid, name)
            excess = len(replicas) - cfg.num_replicas
            if excess > 0:
                staleness = float(resolve_config(
                    cfg.autoscaling_config,
                    cfg.num_replicas)["load_report_staleness_s"])
                with self._lock:
                    a = self._autoscalers.get(name)
                    loads = (a.replica_loads(
                        [i.replica_id for i in replicas], staleness,
                        time.time()) if a is not None else {})
                    victims = pick_scale_down_victims(
                        list(replicas), loads, excess)
                    for info in victims:
                        replicas.remove(info)
                    self._version += 1  # un-route before draining
                    self._ckpt_dirty = True
                self._begin_drain(name, victims,
                                  cfg.graceful_shutdown_timeout_s)
                changed = True
        if changed:
            with self._lock:
                self._version += 1
        self._publish_gauges()
        if self._ckpt_dirty:
            self._save_checkpoint()

    def _publish_gauges(self) -> None:
        # Replica-count gauges per deployment (serve Grafana dashboard);
        # atomically replaced so deleted deployments drop out of the series
        # without a clear-then-set window a concurrent flush could snapshot.
        from ray_tpu.util import metrics as um

        with self._lock:
            counts = {name: len(infos)
                      for name, infos in self._replicas.items()
                      if name in self._deployments}
            targets = {name: d["config"].num_replicas
                       for name, d in self._deployments.items()}
        um.get_gauge(
            "ray_tpu_serve_replicas",
            "Running replicas per serve deployment",
            tag_keys=("deployment",),
        ).set_many([({"deployment": name}, float(n))
                    for name, n in counts.items()])
        um.get_gauge(
            "ray_tpu_serve_autoscale_desired",
            "Autoscaler-desired replica count per serve deployment",
            tag_keys=("deployment",),
        ).set_many([({"deployment": name}, float(n))
                    for name, n in targets.items()])
        um.get_gauge(
            "ray_tpu_serve_autoscale_actual",
            "Actual replica count per serve deployment",
            tag_keys=("deployment",),
        ).set_many([({"deployment": name}, float(counts.get(name, 0)))
                    for name in targets])

    # ------------------------------------------------------------------
    # Drain / teardown
    # ------------------------------------------------------------------
    def _begin_drain(self, name: str, infos: List[ReplicaInfo],
                     grace_s: float) -> None:
        """Reconcile-path drain: runs on a background thread so a victim
        dying mid-`prepare_for_shutdown` (or just being slow) can never
        stall the reconcile cadence — the caller already un-routed the
        victims and bumped the version."""
        def run():
            self._drain_and_kill(infos, grace_s)
            with self._lock:
                a = self._autoscalers.get(name)
                if a is not None:
                    for info in infos:
                        a.forget_replica(info.replica_id)

        threading.Thread(target=run, daemon=True,
                         name="serve-drain").start()

    def _drain_and_kill(self, infos: List[ReplicaInfo],
                        grace_s: float) -> None:
        """Graceful teardown (reference: replica.py
        perform_graceful_shutdown): each victim stops admitting — new
        requests shed with BackPressureError, so handles re-route them to
        surviving replicas — and we wait out its in-flight requests before
        the kill. Callers must already have bumped the routing version with
        the victim removed. Drains fan out in parallel; a dead or wedged
        replica just falls through to the kill."""
        refs = []
        for info in infos:
            try:
                refs.append(
                    (info,
                     info.actor.prepare_for_shutdown.remote(grace_s)))
            except Exception:
                refs.append((info, None))
        for info, ref in refs:
            if ref is not None:
                try:
                    left = ray_tpu.get(ref, timeout=grace_s + 10)
                    if left:
                        logger.warning(
                            "replica %s killed with %d requests still "
                            "in flight after %.1fs grace",
                            info.replica_id, left, grace_s)
                except Exception:
                    pass
            self._kill(info)

    def _kill(self, info: ReplicaInfo) -> None:
        try:
            ray_tpu.kill(info.actor)
        except Exception:
            pass
