"""gRPC ingress proxy (reference: serve/_private/proxy.py:521 gRPCProxy).

Shares the router/handle plane with the HTTP proxy: the same controller
routing table maps application names to deployments, and requests ride
the same DeploymentHandle path (power-of-two replica choice, autoscaling
stats). The wire contract is serve_grpc.proto — a generic bytes service
routed by application name (the reference mounts user-defined servicers;
this framework's xlang stance is bytes-in/bytes-out with client-side
encoding). Unary Predict hits the root deployment's __call__;
PredictStream emits one reply per generator item."""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, Optional

import ray_tpu
from ray_tpu.exceptions import (
    GetTimeoutError,
    NoHealthyReplicasError,
    RayActorError,
    unwrap_backpressure,
)
from ray_tpu.serve._common import CONTROLLER_NAME
from ray_tpu.serve._proxy import RequestAccount
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _grpc_overload_status(e: BaseException):
    """(grpc.StatusCode, shed_reason) for overload-control failures, or
    (None, None) for everything else — mirrors the HTTP proxy's
    429/504/503 contract on the gRPC plane."""
    import grpc

    if unwrap_backpressure(e) is not None:
        return grpc.StatusCode.RESOURCE_EXHAUSTED, "backpressure"
    if isinstance(e, (GetTimeoutError, asyncio.TimeoutError, TimeoutError)):
        return grpc.StatusCode.DEADLINE_EXCEEDED, "timeout"
    if isinstance(e, NoHealthyReplicasError):
        return grpc.StatusCode.UNAVAILABLE, "no_replica"
    if isinstance(e, RayActorError) or isinstance(
            getattr(e, "cause", None), RayActorError):
        return grpc.StatusCode.UNAVAILABLE, "replica_died"
    return None, None


def _client_request_id(context) -> str:
    """The client's `x-request-id` metadata, "" without one."""
    for key, value in context.invocation_metadata() or ():
        if key == "x-request-id":
            return value if isinstance(value, str) else value.decode()
    return ""


def _decode_payload(request) -> Any:
    if request.content_type == "application/json" or (
            not request.content_type and request.payload[:1] in (b"{", b"[")):
        try:
            return json.loads(request.payload)
        except Exception:  # noqa: BLE001
            pass
    return bytes(request.payload)


def _encode_payload(value, pb) -> Any:
    if isinstance(value, (bytes, bytearray, memoryview)):
        return pb.PredictReply(payload=bytes(value),
                               content_type="application/octet-stream")
    return pb.PredictReply(payload=json.dumps(value).encode(),
                           content_type="application/json")


class GrpcProxyActor:
    """Async actor hosting a grpc.aio server next to the HTTP proxy."""

    def __init__(self, port: int = 0):
        self._port = port
        self._routes: Dict[str, str] = {}  # route_prefix -> deployment
        self._apps: Dict[str, str] = {}    # app/deployment name -> deployment
        self._handles: Dict[str, Any] = {}
        self._deployments: Dict[str, Any] = {}  # name -> routing info
        self._version = -1
        self._server = None
        self._streams = 0  # PredictStream calls open
        # deployment -> sheds since the last delivered ingress report.
        self._shed_accum: Dict[str, int] = {}
        from ray_tpu.util import metrics as um

        self._m_shed = um.get_counter(
            "ray_tpu_serve_shed_total",
            "Serve requests shed by overload control, by stage/reason",
            tag_keys=("deployment", "reason"))

    def _timeout_for(self, name: str) -> float:
        info = self._deployments.get(name) or {}
        try:
            return float(info.get("request_timeout_s", 60.0))
        except (TypeError, ValueError):
            return 60.0

    async def start(self) -> int:
        import grpc

        from ray_tpu.serve import serve_grpc_pb2 as pb
        from ray_tpu.serve import serve_grpc_pb2_grpc as pb_grpc

        proxy = self

        class Servicer(pb_grpc.RayTpuServeServicer):
            async def Predict(self, request, context):
                handle = await proxy._resolve(request.application)
                if handle is None:
                    await context.abort(
                        grpc.StatusCode.NOT_FOUND,
                        f"no application {request.application!r}")
                loop = asyncio.get_running_loop()
                name = handle.deployment_name
                timeout_s = proxy._timeout_for(name)
                acct = RequestAccount(_client_request_id(context), False,
                                      proxy._streams)
                handle = handle._for_request(acct.rid, acct.t_read)
                try:
                    payload = _decode_payload(request)
                    out = await asyncio.wait_for(
                        loop.run_in_executor(None, acct.pooled(
                            lambda: handle.remote(payload).result(
                                timeout=timeout_s))),
                        timeout_s + 5.0)
                    acct.took_item()
                    acct.status = "OK"
                    return _encode_payload(out, pb)
                except Exception as e:  # noqa: BLE001
                    code, reason = _grpc_overload_status(e)
                    code = code or grpc.StatusCode.INTERNAL
                    acct.status = code.name
                    if reason is not None:
                        proxy._shed(name, reason)
                    await context.abort(code, repr(e))
                finally:
                    acct.close(handle)

            async def PredictStream(self, request, context):
                handle = await proxy._resolve(request.application)
                if handle is None:
                    await context.abort(
                        grpc.StatusCode.NOT_FOUND,
                        f"no application {request.application!r}")
                loop = asyncio.get_running_loop()
                name = handle.deployment_name
                acct = RequestAccount(_client_request_id(context), True,
                                      proxy._streams)
                handle = handle.options(stream=True)._for_request(
                    acct.rid, acct.t_read)
                payload = _decode_payload(request)
                proxy._streams += 1
                try:
                    gen = await loop.run_in_executor(None, handle.remote,
                                                     payload)
                    it = iter(gen)
                    _END = object()

                    def _next():
                        try:
                            item = next(it)
                        except StopIteration:
                            return _END
                        acct.took_item()
                        return item

                    while True:
                        try:
                            item = await asyncio.wait_for(
                                loop.run_in_executor(
                                    None, acct.pooled(_next)),
                                proxy._timeout_for(name) + 5.0)
                        except Exception as e:  # noqa: BLE001
                            code, reason = _grpc_overload_status(e)
                            acct.status = (
                                code or grpc.StatusCode.UNKNOWN).name
                            if code is not None and not acct.items:
                                proxy._shed(name, reason)
                                await context.abort(code, repr(e))
                            raise
                        if item is _END:
                            acct.status = "OK"
                            return
                        reply = _encode_payload(item, pb)
                        t_write = time.perf_counter_ns()
                        yield reply  # back here when gRPC has taken it
                        acct.wrote(len(reply.payload), t_write)
                finally:
                    # 0 still: the client cancelled, the handler was closed
                    acct.status = acct.status or "CANCELLED"
                    proxy._streams -= 1
                    acct.close(handle)

            async def ListApplications(self, request, context):
                await proxy._force_refresh()
                return pb.ListApplicationsReply(
                    application_names=sorted(proxy._apps))

            async def Healthz(self, request, context):
                return pb.HealthzReply(message="success")

        self._server = grpc.aio.server()
        pb_grpc.add_RayTpuServeServicer_to_server(Servicer(), self._server)
        self._port = self._server.add_insecure_port(
            f"127.0.0.1:{self._port}")
        await self._server.start()
        asyncio.ensure_future(self._route_refresh_loop())
        logger.info("serve gRPC proxy listening on %d", self._port)
        return self._port

    def port(self) -> int:
        return self._port

    def _shed(self, deployment: str, reason: str) -> None:
        self._m_shed.inc(tags={"deployment": deployment, "reason": reason})
        self._shed_accum[deployment] = (
            self._shed_accum.get(deployment, 0) + 1)

    def _take_ingress_report(self) -> Optional[Dict[str, Any]]:
        if not self._shed_accum:
            return None
        accum, self._shed_accum = self._shed_accum, {}
        return {"reporter": f"grpc-proxy:{self._port}",
                "deployments": {name: {"queued": 0, "shed_delta": d}
                                for name, d in accum.items()}}

    def _restore_ingress_report(self,
                                report: Optional[Dict[str, Any]]) -> None:
        if not report:
            return
        for name, rep in report["deployments"].items():
            self._shed_accum[name] = (self._shed_accum.get(name, 0)
                                      + rep["shed_delta"])

    # -- routing shared with the HTTP plane ----------------------------
    async def _route_refresh_loop(self) -> None:
        loop = asyncio.get_running_loop()
        # Re-resolve the controller handle after any failure (same fix as
        # the HTTP proxy): polling a dead handle forever left the proxy
        # blind across controller restarts.
        controller = None
        while True:
            try:
                if controller is None:
                    controller = await loop.run_in_executor(
                        None, lambda: ray_tpu.get_actor(CONTROLLER_NAME))
                    self._controller = controller
                report = self._take_ingress_report()
                try:
                    routing = await controller.get_routing.remote(
                        self._version, report)
                except Exception:
                    self._restore_ingress_report(report)
                    raise
                self._apply_routing(routing)
            except Exception:
                if controller is not None:
                    logger.warning("grpc route refresh failed; will "
                                   "re-resolve controller", exc_info=True)
                controller = None
            await asyncio.sleep(1.0)

    def _apply_routing(self, routing) -> None:
        from ray_tpu.serve._handle import DeploymentHandle

        if routing is None:
            return
        self._version = routing["version"]
        self._deployments = routing["deployments"]
        apps: Dict[str, str] = {}
        for name, info in routing["deployments"].items():
            if info.get("route_prefix"):
                apps[name] = name
            if name not in self._handles:
                self._handles[name] = DeploymentHandle(name)
        self._apps = apps

    async def _force_refresh(self) -> None:
        controller = getattr(self, "_controller", None)
        if controller is None:
            return
        try:
            self._apply_routing(await controller.get_routing.remote(-1))
        except Exception:
            logger.exception("forced grpc route refresh failed")

    async def _resolve(self, application: str) -> Optional[Any]:
        if application not in self._apps:
            await self._force_refresh()
        name = self._apps.get(application)
        if name is None and application in self._handles:
            name = application  # direct deployment-name addressing
        return self._handles.get(name) if name else None
