"""Replica actor: hosts one copy of the user's deployment callable.

Reference: serve/_private/replica.py:918 (`ReplicaActor`) + `UserCallableWrapper`
(:1165). Redesign: the replica is a plain async actor; request concurrency is
the actor's max_concurrency; streaming responses use the runtime's native
streaming generators instead of a bespoke ASGI bridge."""

from __future__ import annotations

import inspect
import time
from typing import Any, Dict, Optional, Tuple

from ray_tpu._private import flight_recorder as _fr


# How often a serve-managed replica pushes its load report to the
# controller (the primary autoscaling signal; check_health piggyback is
# the fallback when this thread is partitioned away).
REPORT_PERIOD_S = 0.5


class ReplicaActor:
    def __init__(self, serialized_ctor, init_args: Tuple, init_kwargs: Dict,
                 user_config: Optional[Dict[str, Any]] = None,
                 deployment_name: str = "",
                 max_ongoing_requests: int = 0,
                 replica_id: str = ""):
        import cloudpickle

        ctor = cloudpickle.loads(serialized_ctor)
        if inspect.isclass(ctor):
            self._callable = ctor(*init_args, **init_kwargs)
        else:
            # Function deployment: the function IS the handler.
            self._callable = ctor
        self._user_config = user_config
        if user_config is not None:
            reconfigure = getattr(self._callable, "reconfigure", None)
            if callable(reconfigure):
                reconfigure(user_config)
        import threading

        self._ongoing = 0
        self._ongoing_lock = threading.Lock()
        # Hard admission cap (reference: replica_scheduler queue_len-based
        # acceptance): 0 = unbounded (legacy direct-actor use); over-cap
        # requests are SHED with BackPressureError instead of silently
        # queueing in the actor mailbox past max_ongoing_requests.
        self._max_ongoing = max(0, int(max_ongoing_requests))
        # Draining: set by prepare_for_shutdown before the controller kills
        # this replica; new requests shed, in-flight ones run to completion.
        self._draining = False
        # Sheds since the last load report was taken (push or health
        # piggyback): the controller turns these deltas into the shed-rate
        # autoscaling term.
        self._shed_since_report = 0
        self._replica_id = replica_id
        # Serve request metrics (reference: serve/_private/metrics —
        # the names the shipped Grafana serve dashboard charts). Counted
        # here, at the replica, so handle calls and HTTP both register.
        self._deployment_name = deployment_name
        from ray_tpu.util import metrics as um

        self._m_requests = um.get_counter(
            "ray_tpu_serve_requests_total",
            "Serve requests handled, by deployment and outcome",
            tag_keys=("deployment", "status"))
        self._m_latency = um.get_histogram(
            "ray_tpu_serve_latency_seconds",
            "Serve request latency at the replica",
            tag_keys=("deployment",))
        self._m_ongoing = um.get_gauge(
            "ray_tpu_serve_ongoing_requests",
            "Requests currently executing in this replica "
            "(the autoscaling signal)",
            tag_keys=("deployment", "replica"))
        self._m_shed = um.get_counter(
            "ray_tpu_serve_shed_total",
            "Serve requests shed by overload control, by stage/reason",
            tag_keys=("deployment", "reason"))
        # Push-based load reporting: only when serve-managed (a
        # deployment name AND replica id were assigned by the controller).
        # Direct ReplicaActor use (legacy/tests) has no controller to
        # report to.
        if deployment_name and replica_id:
            threading.Thread(target=self._report_loop, daemon=True,
                             name="serve-replica-report").start()

    def _resolve_method(self, method_name: str):
        if callable(self._callable) and method_name == "__call__":
            return self._callable
        fn = getattr(self._callable, method_name, None)
        if fn is None:
            raise AttributeError(f"deployment has no method {method_name!r}")
        return fn

    def _arrived(self, ctx: Optional[Dict[str, Any]]) -> None:
        """First line of both entries, on the handler thread: the end of a
        request's way in. `pre_ms` is the caller's own account (request read
        to the call handed to the runtime, `_handle.py`), `dispatch_ms` this
        worker's (receipt of the actor call to here); neither subtracts one
        process's clock from another's. Names the request for the marks
        behind this one on the thread: the engine's, the stream's. A call
        made on the actor itself, past every handle, is no request."""
        rid = (ctx or {}).get("rid")
        if not rid:
            return
        _fr.set_request_id(rid)
        entry_ns = _fr.task_entry_ns()
        _fr.mark("ray_tpu.request.arrived", rid=rid,
                 pre_ms=ctx.get("pre_ms", 0.0),
                 dispatch_ms=((time.perf_counter_ns() - entry_ns) / 1e6
                              if entry_ns else 0.0),
                 ongoing=self._ongoing)

    def handle_request(self, method_name: str, args: Tuple, kwargs: Dict,
                       ctx: Optional[Dict[str, Any]] = None):
        """Streaming entry (called with num_returns="dynamic")."""
        self._arrived(ctx)
        with self._track(), self._request_ctx(ctx):
            result = self._resolve_method(method_name)(*args, **kwargs)
            if inspect.isgenerator(result):
                # Streamed via num_returns="dynamic" at the call site.
                yield from result
                return
            yield result

    def handle_request_unary(self, method_name: str, args: Tuple,
                             kwargs: Dict,
                             ctx: Optional[Dict[str, Any]] = None):
        self._arrived(ctx)
        with self._track(), self._request_ctx(ctx):
            return self._resolve_method(method_name)(*args, **kwargs)

    @staticmethod
    def _request_ctx(ctx: Optional[Dict[str, Any]]):
        """Install per-request serve context (today: the multiplexed model
        id read by serve.get_multiplexed_model_id)."""
        import contextlib

        @contextlib.contextmanager
        def cm():
            token = None
            model_id = (ctx or {}).get("multiplexed_model_id")
            if model_id:
                from ray_tpu.serve.multiplex import _set_current_model_id

                token = _set_current_model_id(model_id)
            try:
                yield
            finally:
                if token is not None:
                    from ray_tpu.serve.multiplex import _current_model_id

                    _current_model_id.reset(token)

        return cm()

    def _track(self):
        import contextlib
        import os

        @contextlib.contextmanager
        def cm():
            t0 = time.monotonic()
            dep = self._deployment_name
            gauge_tags = {"deployment": dep, "replica": str(os.getpid())}
            # gauge.set stays INSIDE the lock: counter updates and their
            # gauge publication must be atomic, or two racing finishes can
            # publish out of order and pin a stale nonzero value.
            with self._ongoing_lock:
                # Admission check is atomic with the increment — two
                # racing over-cap requests must not both slip under it.
                if self._draining:
                    self._shed_since_report += 1
                    self._m_shed.inc(tags={"deployment": dep,
                                           "reason": "replica_draining"})
                    from ray_tpu.exceptions import BackPressureError

                    raise BackPressureError(
                        f"replica of {dep!r} is draining for shutdown")
                if self._max_ongoing and self._ongoing >= self._max_ongoing:
                    self._shed_since_report += 1
                    self._m_shed.inc(tags={"deployment": dep,
                                           "reason": "replica_capacity"})
                    from ray_tpu.exceptions import BackPressureError

                    raise BackPressureError(
                        f"replica of {dep!r} at max_ongoing_requests="
                        f"{self._max_ongoing}")
                self._ongoing += 1
                self._m_ongoing.set(self._ongoing, tags=gauge_tags)
            ok = True
            try:
                yield
            except BaseException:
                ok = False
                raise
            finally:
                with self._ongoing_lock:
                    self._ongoing -= 1
                    self._m_ongoing.set(self._ongoing, tags=gauge_tags)
                self._m_requests.inc(tags={
                    "deployment": dep,
                    "status": "ok" if ok else "error"})
                self._m_latency.observe(time.monotonic() - t0,
                                        tags={"deployment": dep})

        return cm()

    def num_ongoing_requests(self) -> int:
        with self._ongoing_lock:
            return self._ongoing

    # -- load reporting (the push half of the autoscaling signal) --------
    def _take_load_report(self) -> Dict[str, Any]:
        """Atomically snapshot ongoing + consume the shed delta. Callers
        that fail to DELIVER the report must give the delta back via
        _restore_shed_delta, or those sheds vanish from the signal."""
        with self._ongoing_lock:
            delta = self._shed_since_report
            self._shed_since_report = 0
            return {"ongoing": self._ongoing, "shed_delta": delta,
                    "draining": self._draining}

    def _restore_shed_delta(self, delta: int) -> None:
        if delta > 0:
            with self._ongoing_lock:
                self._shed_since_report += delta

    def _report_loop(self) -> None:
        """Push `{ongoing, shed_delta}` to the controller every
        REPORT_PERIOD_S. The delivery is confirmed (get with a short
        timeout) so a failed push restores its shed delta; the controller
        handle is re-resolved after any failure — it survives controller
        restarts by name."""
        from ray_tpu._private.backoff import delay_for_attempt
        from ray_tpu.serve._common import CONTROLLER_NAME

        import ray_tpu

        controller = None
        failures = 0
        while True:
            report = None
            try:
                if controller is None:
                    controller = ray_tpu.get_actor(CONTROLLER_NAME)
                report = self._take_load_report()
                ray_tpu.get(
                    controller.report_replica_load.remote(
                        self._deployment_name, self._replica_id,
                        report["ongoing"], report["shed_delta"]),
                    timeout=5)
                failures = 0
                time.sleep(REPORT_PERIOD_S)
            except Exception:
                if report is not None:
                    self._restore_shed_delta(report["shed_delta"])
                controller = None
                failures += 1
                time.sleep(delay_for_attempt(failures - 1,
                                             initial=0.2, maximum=5.0))

    def prepare_for_shutdown(self, timeout_s: float = 10.0) -> int:
        """Graceful drain (reference: replica.py perform_graceful_shutdown):
        stop admitting — new requests shed with BackPressureError so the
        handle re-routes them — then wait for in-flight requests to finish,
        up to ``timeout_s``. Returns the number still in flight at the end
        (0 = fully drained); the controller kills the actor either way.
        Runs on an executor thread, so in-flight request threads proceed."""
        with self._ongoing_lock:
            self._draining = True
        deadline = time.monotonic() + max(0.0, timeout_s)
        while time.monotonic() < deadline:
            with self._ongoing_lock:
                if self._ongoing == 0:
                    return 0
            time.sleep(0.02)
        with self._ongoing_lock:
            return self._ongoing

    def reconfigure(self, user_config: Dict[str, Any]) -> None:
        reconfigure = getattr(self._callable, "reconfigure", None)
        if callable(reconfigure):
            reconfigure(user_config)

    def check_health(self) -> Dict[str, Any]:
        """Health verdict with the load report piggybacked (reference:
        autoscaling metrics ride the replica's existing control channel) —
        the controller's poll-based fallback signal when the push thread
        is partitioned away. Raises if the user check raises (unhealthy);
        a dict return is truthy, so bool-expecting callers still work."""
        user_check = getattr(self._callable, "check_health", None)
        if callable(user_check):
            user_check()
        rep = self._take_load_report()
        return {"healthy": True, "ongoing": rep["ongoing"],
                "shed_delta": rep["shed_delta"],
                "draining": rep["draining"]}
