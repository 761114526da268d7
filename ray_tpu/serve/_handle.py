"""DeploymentHandle: the client-side router.

Reference: serve/handle.py:639 (`DeploymentHandle`), _private/router.py:341,
request_router/pow_2_router.py (power-of-two-choices replica picking).
Redesign: routing state lives in the handle itself — it caches the
controller's routing table by version and tracks its own outstanding count
per replica; two random replicas are compared by load per request."""

from __future__ import annotations

import random
import threading
import time
import uuid
from typing import Any, Dict, Optional

import ray_tpu
from ray_tpu.exceptions import (
    BackPressureError,
    NoHealthyReplicasError,
    unwrap_backpressure,
)
from ray_tpu.serve._common import CONTROLLER_NAME

_ROUTING_TTL_S = 2.0

# Serve-wide shutdown latch: serve.shutdown() sets it so every handle's
# long-poll thread exits instead of spinning forever retrying a controller
# that is gone for good; serve.start() clears it for the next lifecycle.
_shutdown_event = threading.Event()


def signal_shutdown() -> None:
    _shutdown_event.set()


def reset_shutdown() -> None:
    _shutdown_event.clear()


class _RouterCache:
    def __init__(self):
        self.version = -1
        self.deployments: Dict[str, Any] = {}
        self.fetched_at = 0.0
        self.outstanding: Dict[str, int] = {}
        # Requests parked in backpressure-retry (the handle's bounded
        # pending queue; see DeploymentConfig.max_queued_requests).
        self.queued = 0
        # Terminal sheds (queue full / deadline) since the last load
        # report delivered to the controller — piggybacked on the
        # long-poll as part of the autoscaling signal.
        self.shed_delta = 0
        self.reporter = "handle:" + uuid.uuid4().hex[:8]
        # Multiplexing affinity: model_id -> replica_id last used for it
        # (reference: the router prefers replicas with the model loaded).
        self.model_replica: Dict[str, str] = {}
        self.lock = threading.Lock()
        self.poller_started = False


class DeploymentResponse:
    """Future-like wrapper over the underlying ObjectRef(s).

    Backpressure contract: a replica at max_ongoing_requests sheds with
    BackPressureError instead of queueing. result() absorbs those sheds —
    the request enters the handle's bounded pending queue and is retried
    against a freshly pow-2-picked replica with jittered backoff — and
    re-raises BackPressureError to the caller only once the queue is full
    or the deadline passes (reference: router retry + SEDA admission)."""

    def __init__(self, ref, handle: "DeploymentHandle", replica_id: str,
                 call_args: tuple = (), call_kwargs: Optional[dict] = None):
        self._ref = ref
        self._handle = handle
        self._replica_id = replica_id
        self._call_args = call_args
        self._call_kwargs = call_kwargs or {}
        self._done = False

    def result(self, timeout: Optional[float] = None) -> Any:
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        try:
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            return ray_tpu.get(self._ref, timeout=remaining)
        except Exception as e:
            if unwrap_backpressure(e) is None:
                raise
            self._finish()  # release the shed attempt's outstanding slot
            out, self._ref, self._replica_id = self._handle._retry_shed(
                self._call_args, self._call_kwargs, deadline, e)
            return out
        finally:
            self._finish()

    def _finish(self):
        if not self._done:
            self._done = True
            self._handle._dec(self._replica_id)

    @property
    def ref(self):
        return self._ref

    @property
    def request_id(self) -> str:
        """The `rid` of this request's marks in every process it crosses."""
        return self._handle._request_id

    def __del__(self):
        self._finish()


class DeploymentResponseGenerator:
    """Streaming response: iterate the replica's generator items. A shed
    (BackPressureError before the first item) re-picks a replica through
    the same bounded-queue retry path as unary calls."""

    def __init__(self, gen, handle: "DeploymentHandle", replica_id: str,
                 call_args: tuple = (), call_kwargs: Optional[dict] = None):
        self._gen = gen
        self._handle = handle
        self._replica_id = replica_id
        self._call_args = call_args
        self._call_kwargs = call_kwargs or {}
        self._done = False

    @property
    def request_id(self) -> str:
        """The `rid` of this request's marks in every process it crosses."""
        return self._handle._request_id

    def __iter__(self):
        attempts = 0
        deadline = None
        try:
            first = True
            it = iter(self._gen)
            while True:
                try:
                    ref = next(it)
                except StopIteration:
                    return
                try:
                    item = ray_tpu.get(ref)
                except Exception as e:
                    if not first or unwrap_backpressure(e) is None:
                        raise
                    # Shed before any output: retry on another replica.
                    self._handle._dec(self._replica_id)
                    self._done = True  # old slot released; guard finally
                    if deadline is None:
                        deadline = (time.monotonic()
                                    + self._handle._request_timeout_s())
                    rid2, gen2 = self._handle._retry_shed_stream(
                        self._call_args, self._call_kwargs, deadline,
                        attempts, e)
                    self._done = False
                    attempts += 1
                    self._gen, self._replica_id = gen2, rid2
                    it = iter(self._gen)
                    continue
                first = False
                yield item
        finally:
            if not self._done:
                self._done = True
                self._handle._dec(self._replica_id)


class DeploymentHandle:
    def __init__(self, deployment_name: str, method_name: str = "__call__",
                 stream: bool = False, multiplexed_model_id: str = "",
                 _cache: Optional[_RouterCache] = None):
        self.deployment_name = deployment_name
        self._method_name = method_name
        self._stream = stream
        self._multiplexed_model_id = multiplexed_model_id
        # Variants of one handle (`options`) share its router state.
        self._cache = _cache or _RouterCache()
        # One request's variant (`_for_request`) alone has these: the id its
        # marks carry in every process, time.perf_counter() when the request
        # was read, and, once submitted, its `pre_ms`.
        self._request_id = ""
        self._t_read = 0.0
        self._pre_ms = 0.0

    # -- fluent API (reference: handle.options / method access) ----------
    def options(self, *, method_name: Optional[str] = None,
                stream: Optional[bool] = None,
                multiplexed_model_id: Optional[str] = None
                ) -> "DeploymentHandle":
        return DeploymentHandle(
            self.deployment_name,
            method_name if method_name is not None else self._method_name,
            self._stream if stream is None else stream,
            self._multiplexed_model_id if multiplexed_model_id is None
            else multiplexed_model_id, self._cache)

    def __getattr__(self, name: str) -> "DeploymentHandle":
        if name.startswith("_"):
            raise AttributeError(name)
        return self.options(method_name=name)

    def _for_request(self, request_id: str,
                     t_read: float) -> "DeploymentHandle":
        """This handle for one request: a proxy gives the id it made (or its
        client's) and when it read the request; `remote` on a bare handle
        makes both. Every attempt of the request goes out under them."""
        h = self.options()
        h._request_id, h._t_read = request_id, t_read
        return h

    # -- routing ---------------------------------------------------------
    # The controller PUSHES table changes through a long-poll kept open by
    # a background thread (reference: long_poll.py LongPollClient); the
    # TTL re-fetch remains only as the bootstrap/fallback path, so scale
    # events reach handles in ~100ms instead of up to _ROUTING_TTL_S.
    def _ensure_poller(self) -> None:
        c = self._cache
        with c.lock:
            if c.poller_started:
                return
            c.poller_started = True
        threading.Thread(target=self._poll_loop, daemon=True,
                         name="serve-router-longpoll").start()

    def _take_load_report(self) -> Dict[str, Any]:
        """Queue depth + terminal-shed delta for this deployment,
        piggybacked on the routing long-poll (the handle tier's half of
        the autoscaling signal — no extra RPC stream). The shed delta is
        CONSUMED here; a failed delivery must give it back."""
        c = self._cache
        with c.lock:
            delta, c.shed_delta = c.shed_delta, 0
            queued = c.queued
        return {"reporter": c.reporter,
                "deployments": {self.deployment_name: {
                    "queued": queued, "shed_delta": delta}}}

    def _restore_load_report(self, report: Dict[str, Any]) -> None:
        c = self._cache
        delta = report["deployments"][self.deployment_name]["shed_delta"]
        if delta:
            with c.lock:
                c.shed_delta += delta

    def _poll_loop(self) -> None:
        c = self._cache
        try:
            while True:
                if _shutdown_event.is_set() or not ray_tpu.is_initialized():
                    return
                report = None
                try:
                    controller = ray_tpu.get_actor(CONTROLLER_NAME)
                    report = self._take_load_report()
                    # The long-poll parks in the controller for up to 25s.
                    # It MUST ride its own submission lane: batched with an
                    # ordinary call (get_http_port, deploy, ...) the shared
                    # reply frame would hold that call hostage for the full
                    # poll window.
                    routing = ray_tpu.get(
                        controller.wait_routing.options(
                            concurrency_group="_serve_longpoll",
                        ).remote(c.version, 25.0, report),
                        timeout=40)
                    if routing is not None:
                        with c.lock:
                            c.version = routing["version"]
                            c.deployments = routing["deployments"]
                            c.fetched_at = time.monotonic()
                except Exception:
                    if report is not None:
                        self._restore_load_report(report)
                    # Controller restarting: back off, retry — but a
                    # serve.shutdown() means it is gone for GOOD; without
                    # the latch check this thread would spin forever.
                    if _shutdown_event.wait(1.0):
                        return
        finally:
            # Allow a later serve.start() to restart the poller on this
            # (cached, shared) router state.
            with c.lock:
                c.poller_started = False

    def _refresh(self, force: bool = False) -> None:
        c = self._cache
        now = time.monotonic()
        if not force and c.deployments and (
                c.poller_started or now - c.fetched_at < _ROUTING_TTL_S):
            return
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        routing = ray_tpu.get(
            controller.get_routing.remote(c.version if not force else -1),
            timeout=30)
        with c.lock:
            c.fetched_at = now
            if routing is not None:
                c.version = routing["version"]
                c.deployments = routing["deployments"]
        self._ensure_poller()

    def _pick_replica(self, args: tuple = (), kwargs: Optional[dict] = None,
                      wait_deadline: Optional[float] = None):
        c = self._cache
        deadline = (time.monotonic() + 30 if wait_deadline is None
                    else wait_deadline)
        while True:
            self._refresh()
            info = c.deployments.get(self.deployment_name)
            replicas = info["replicas"] if info else []
            if replicas:
                break
            if time.monotonic() > deadline:
                raise NoHealthyReplicasError(
                    f"no healthy replicas for deployment "
                    f"{self.deployment_name!r}")
            time.sleep(0.1)
            self._refresh(force=True)
        router = (info or {}).get("request_router", "pow2")
        max_ongoing = int((info or {}).get("max_ongoing_requests", 16))
        with c.lock:
            rid_actor = None
            if self._multiplexed_model_id:
                # Affinity: reuse the replica that last served this model —
                # its LRU cache has the weights in HBM. Overload escape
                # (same rule as _prefix_pick): a hot model must spill to
                # other replicas rather than queue unboundedly on one.
                want = c.model_replica.get(self._multiplexed_model_id)
                floor = min((c.outstanding.get(r[0], 0) for r in replicas),
                            default=0)
                for r in replicas:
                    if r[0] == want:
                        load = c.outstanding.get(want, 0)
                        if load - floor < max(2, max_ongoing // 2):
                            rid_actor = r
                        break
            if rid_actor is None and router == "prefix":
                rid_actor = _prefix_pick(
                    replicas, args, kwargs or {}, c.outstanding, max_ongoing)
            if rid_actor is None:
                if len(replicas) == 1:
                    rid_actor = replicas[0]
                else:
                    # Power of two choices by local outstanding count.
                    a, b = random.sample(replicas, 2)
                    rid_actor = min(
                        (a, b), key=lambda r: c.outstanding.get(r[0], 0))
            rid, actor = rid_actor
            if self._multiplexed_model_id:
                c.model_replica[self._multiplexed_model_id] = rid
            c.outstanding[rid] = c.outstanding.get(rid, 0) + 1
        return rid, actor

    def _dec(self, replica_id: str) -> None:
        c = self._cache
        with c.lock:
            n = c.outstanding.get(replica_id, 0)
            if n > 0:
                c.outstanding[replica_id] = n - 1

    def _deployment_info(self) -> Dict[str, Any]:
        return self._cache.deployments.get(self.deployment_name) or {}

    def _request_timeout_s(self) -> float:
        return float(self._deployment_info().get("request_timeout_s", 60.0))

    # -- invocation ------------------------------------------------------
    def _invoke_once(self, args: tuple, kwargs: dict,
                     wait_deadline: Optional[float] = None):
        """One pick+submit attempt; outstanding[rid] is incremented and the
        caller owns decrementing it when the call completes."""
        rid, actor = self._pick_replica(args, kwargs, wait_deadline)
        # The request's way in as far as this process saw it: read (parse,
        # the proxy's executor hop, the pick above) to handed to the
        # runtime. The replica states it in `ray_tpu.request.arrived`.
        self._pre_ms = (time.perf_counter() - self._t_read) * 1e3
        ctx = {"rid": self._request_id, "pre_ms": self._pre_ms}
        if self._multiplexed_model_id:
            ctx["multiplexed_model_id"] = self._multiplexed_model_id
        try:
            if self._stream:
                out = actor.handle_request.options(
                    num_returns="dynamic").remote(
                        self._method_name, args, kwargs, ctx)
            else:
                out = actor.handle_request_unary.remote(
                    self._method_name, args, kwargs, ctx)
            return rid, out
        except Exception:
            self._dec(rid)
            raise

    def remote(self, *args, **kwargs):
        h = self if self._request_id else self._for_request(
            uuid.uuid4().hex[:12], time.perf_counter())
        rid, out = h._invoke_once(args, kwargs)
        if h._stream:
            return DeploymentResponseGenerator(out, h, rid, args, kwargs)
        return DeploymentResponse(out, h, rid, args, kwargs)

    # -- backpressure retry (the handle's bounded pending queue) ---------
    def _enter_queue(self, first_exc: Exception) -> None:
        c = self._cache
        max_queued = int(self._deployment_info().get(
            "max_queued_requests", 64))
        with c.lock:
            if c.queued >= max_queued:
                # Terminal shed (counted once, not per retry attempt):
                # demand the replica tier never saw — report it so the
                # autoscaler can turn it into capacity.
                c.shed_delta += 1
                raise BackPressureError(
                    f"pending queue full for deployment "
                    f"{self.deployment_name!r} "
                    f"(max_queued_requests={max_queued})") from first_exc
            c.queued += 1

    def _leave_queue(self) -> None:
        c = self._cache
        with c.lock:
            if c.queued > 0:
                c.queued -= 1

    def queued_requests(self) -> int:
        with self._cache.lock:
            return self._cache.queued

    def _retry_shed(self, args: tuple, kwargs: dict,
                    deadline: Optional[float], first_exc: Exception):
        """Blocking retry after a replica shed the request: hold one
        bounded-queue slot, sleep with jittered exponential backoff, and
        re-submit via a fresh pow-2 pick (the load that caused the shed
        steers the pick away). Raises BackPressureError once the queue is
        full or the deadline passes — never waits unboundedly."""
        from ray_tpu._private.backoff import delay_for_attempt

        if deadline is None:
            deadline = time.monotonic() + self._request_timeout_s()
        self._enter_queue(first_exc)
        try:
            attempt = 0
            while True:
                d = delay_for_attempt(attempt, initial=0.02, maximum=0.5)
                attempt += 1
                if time.monotonic() + d >= deadline:
                    with self._cache.lock:
                        self._cache.shed_delta += 1
                    raise BackPressureError(
                        f"request to {self.deployment_name!r} still shed "
                        f"at deadline after {attempt} attempts"
                    ) from first_exc
                time.sleep(d)
                rid, ref = self._invoke_once(args, kwargs,
                                             wait_deadline=deadline)
                try:
                    out = ray_tpu.get(
                        ref, timeout=max(
                            0.0, deadline - time.monotonic()))
                except Exception as e:
                    self._dec(rid)
                    if unwrap_backpressure(e) is None:
                        raise
                    continue  # shed again: next backoff round
                self._dec(rid)
                return out, ref, rid
        finally:
            self._leave_queue()

    def _retry_shed_stream(self, args: tuple, kwargs: dict,
                           deadline: float, attempt: int,
                           first_exc: Exception):
        """Streaming flavor: one backoff round per call (the iterator owns
        the attempt counter and deadline), returning a fresh generator with
        outstanding[rid] held by the caller."""
        from ray_tpu._private.backoff import delay_for_attempt

        d = delay_for_attempt(attempt, initial=0.02, maximum=0.5)
        if time.monotonic() + d >= deadline:
            with self._cache.lock:
                self._cache.shed_delta += 1
            raise BackPressureError(
                f"stream request to {self.deployment_name!r} still shed "
                f"at deadline") from first_exc
        self._enter_queue(first_exc)
        try:
            time.sleep(d)
        finally:
            self._leave_queue()
        return self._invoke_once(args, kwargs, wait_deadline=deadline)

    def __reduce__(self):
        return (DeploymentHandle,
                (self.deployment_name, self._method_name, self._stream,
                 self._multiplexed_model_id))


def _prefix_pick(replicas, args, kwargs, outstanding, max_ongoing):
    """Prefix-aware pick (reference: request_router/prefix_aware_router.py —
    there for vLLM prefix-cache hits; here for the paged-KV prefix cache):
    requests sharing a prompt prefix rendezvous-hash to the same replica so
    its KV pages stay hot, unless that replica is overloaded relative to the
    least-loaded one."""
    # Explicit None checks: prompts are often numpy arrays, whose truth
    # value (as in `a or b`) raises.
    prompt = kwargs.get("prompt_ids")
    if prompt is None:
        prompt = kwargs.get("prompt")
    if prompt is None and args:
        a0 = args[0]
        if isinstance(a0, dict):
            prompt = a0.get("prompt_ids")
            if prompt is None:
                prompt = a0.get("prompt")
        elif isinstance(a0, (str, list, tuple)):
            prompt = a0
        elif hasattr(a0, "__len__") and not isinstance(a0, (bytes,)):
            prompt = a0  # ndarray of token ids
    if prompt is None:
        return None
    if isinstance(prompt, str):
        key = prompt[:64]
    else:
        try:
            key = ",".join(str(int(t)) for t in list(prompt)[:16])
        except (TypeError, ValueError):
            return None
    import hashlib

    best = max(replicas, key=lambda r: hashlib.blake2b(
        (key + "|" + r[0]).encode(), digest_size=8).digest())
    load = outstanding.get(best[0], 0)
    floor = min(outstanding.get(r[0], 0) for r in replicas)
    if load - floor >= max(2, max_ongoing // 2):
        return None  # overloaded: let pow-2 spread it
    return best
