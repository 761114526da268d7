"""HTTP ingress proxy (reference: serve/_private/proxy.py:697 `HTTPProxy`).

Redesign: a stdlib asyncio HTTP/1.1 server inside an async actor — no
uvicorn/starlette dependency. JSON in/out; streaming handles produce
chunked-transfer responses (one chunk per generator item).

Overload contract (reference: SEDA adaptive admission control, DAGOR):
every queueing stage sheds explicitly instead of collapsing —
* admission ceiling: more than ``max_concurrent_requests`` in flight →
  429 + Retry-After without touching the handle plane;
* replica/handle backpressure (``BackPressureError``) → 429 + Retry-After;
* per-deployment ``request_timeout_s`` expiry → 504;
* dead actor / no healthy replica → 503 + Retry-After;
* oversized body → 413, oversized header block → 431 (connection closed);
every shed increments ``ray_tpu_serve_shed_total{deployment,reason}``.
Liveness (``/-/healthz``) and readiness (``/-/ready``: the route table has
been fetched from the controller at least once, and not draining) are
split so a load balancer never sends traffic to a blind proxy. Shutdown
is drain-aware: ``drain()`` closes the listener first, then waits out
in-flight requests before the controller kills the actor."""

from __future__ import annotations

import asyncio
import json
import time
import uuid
from typing import Any, Callable, Dict, Optional, Tuple

import ray_tpu
from ray_tpu._private import flight_recorder as _fr
from ray_tpu.exceptions import (
    GetTimeoutError,
    NoHealthyReplicasError,
    RayActorError,
    unwrap_backpressure,
)
from ray_tpu.serve._common import CONTROLLER_NAME
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Request-line / header-block parsing bounds (431 beyond them): a
# misbehaving client must not be able to balloon proxy memory with an
# unbounded header flood before admission control ever sees the request.
MAX_HEADER_COUNT = 128
MAX_HEADER_BYTES = 64 * 1024
# Declared-body ceiling (413 beyond it) — checked against content-length
# BEFORE the body is read, so the bytes are never buffered.
MAX_BODY_BYTES = 8 * 1024 * 1024
# Proxy-wide concurrent-request ceiling (429 beyond it).
MAX_CONCURRENT_REQUESTS = 256
# Fallback when a route has no deployment config behind it yet.
DEFAULT_REQUEST_TIMEOUT_S = 60.0

_RETRY_AFTER = b"retry-after: 1\r\n"


class RequestAccount:
    """What one request cost in a proxy (HTTP or gRPC), stated once, in the
    mark `ray_tpu.proxy.request`, when its response is written or has
    failed. Per item it reads the clock and adds; a ring event an item
    would push everything else out of the recorder's ring. All durations
    are of this process's clock; the request's id (`rid`: the client's
    `x-request-id`, else made here) is what joins the mark to the
    replica's `ray_tpu.request.*` and `ray_tpu.stream.sent`."""

    def __init__(self, request_id: str, stream: bool, open_streams: int):
        self.t_read = time.perf_counter()
        self.rid = request_id[:64] or uuid.uuid4().hex[:12]
        self.stream = stream
        self.open_streams = open_streams  # besides this one, at the read
        self.status: Any = 0  # what the client was answered; 0: nothing
        self.items = self.bytes = 0
        self.first_item_ms = 0.0
        self.pool_wait_ns = self.pool_wait_max_ns = 0
        self.next_ns = self.write_ns = 0

    def pooled(self, fn: Callable[[], Any]) -> Callable[[], Any]:
        """`fn` for `run_in_executor`: how long it waited for one of the
        pool's threads (made here, on the loop, to begun there) and how
        long it then ran (`gen_next` and `get` of one item). A request has
        one such call in flight, so the two threads never add at once."""
        t_asked = time.perf_counter_ns()

        def run():
            t_begun = time.perf_counter_ns()
            wait = t_begun - t_asked
            self.pool_wait_ns += wait
            if wait > self.pool_wait_max_ns:
                self.pool_wait_max_ns = wait
            try:
                return fn()
            finally:
                self.next_ns += time.perf_counter_ns() - t_begun

        return run

    def took_item(self) -> None:
        if not self.items:
            self.first_item_ms = (time.perf_counter() - self.t_read) * 1e3
        self.items += 1

    def wrote(self, nbytes: int, t_begun_ns: int) -> None:
        self.bytes += nbytes
        self.write_ns += time.perf_counter_ns() - t_begun_ns

    def close(self, handle) -> None:
        _fr.mark("ray_tpu.proxy.request", rid=self.rid, status=self.status,
                 stream=self.stream, items=self.items, bytes=self.bytes,
                 open_streams=self.open_streams, pre_ms=handle._pre_ms,
                 first_item_ms=self.first_item_ms,
                 pool_wait_ms=self.pool_wait_ns / 1e6,
                 pool_wait_max_ms=self.pool_wait_max_ns / 1e6,
                 next_ms=self.next_ns / 1e6, write_ms=self.write_ns / 1e6,
                 total_ms=(time.perf_counter() - self.t_read) * 1e3)


class ProxyActor:
    def __init__(self, port: int = 0,
                 max_concurrent_requests: int = MAX_CONCURRENT_REQUESTS,
                 max_body_bytes: int = MAX_BODY_BYTES,
                 max_header_bytes: int = MAX_HEADER_BYTES,
                 request_timeout_s: float = DEFAULT_REQUEST_TIMEOUT_S):
        self._port = port
        self._server: Optional[asyncio.AbstractServer] = None
        self._routes: Dict[str, str] = {}  # prefix -> deployment name
        self._deployments: Dict[str, Any] = {}  # name -> routing info
        self._handles: Dict[str, Any] = {}
        self._controller = None
        self._version = -1
        self._max_concurrent = int(max_concurrent_requests)
        self._max_body = int(max_body_bytes)
        self._max_header_bytes = int(max_header_bytes)
        self._default_timeout_s = float(request_timeout_s)
        self._ongoing = 0
        self._streams = 0  # of them, streams open
        self._ready = False
        self._draining = False
        # deployment -> sheds since the last delivered ingress report.
        self._shed_accum: Dict[str, int] = {}
        from ray_tpu.util import metrics as um

        self._m_shed = um.get_counter(
            "ray_tpu_serve_shed_total",
            "Serve requests shed by overload control, by stage/reason",
            tag_keys=("deployment", "reason"))

    async def start(self) -> int:
        self._server = await asyncio.start_server(
            self._on_conn, host="127.0.0.1", port=self._port)
        self._port = self._server.sockets[0].getsockname()[1]
        asyncio.ensure_future(self._route_refresh_loop())
        logger.info("serve HTTP proxy listening on %d", self._port)
        return self._port

    def port(self) -> int:
        return self._port

    async def drain(self, timeout_s: float = 10.0) -> int:
        """Drain-aware shutdown (reference: proxy drain before controller
        kill): close the listener FIRST so no new connection lands, mark
        unready (load balancers stop sending), then wait out in-flight
        requests. Returns how many were still in flight at the end."""
        self._draining = True
        if self._server is not None:
            self._server.close()
            try:
                await self._server.wait_closed()
            except Exception:
                pass
        deadline = time.monotonic() + max(0.0, timeout_s)
        while self._ongoing > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        return self._ongoing

    def _take_ingress_report(self) -> Optional[Dict[str, Any]]:
        """Shed deltas accumulated per deployment since the last delivered
        report — piggybacked on the routing poll so proxy-tier sheds feed
        the autoscaler with no extra RPC stream. None when quiet.
        Event-loop-only state: no lock needed."""
        if not self._shed_accum:
            return None
        accum, self._shed_accum = self._shed_accum, {}
        return {"reporter": f"http-proxy:{self._port}",
                "deployments": {name: {"queued": 0, "shed_delta": d}
                                for name, d in accum.items()}}

    def _restore_ingress_report(self,
                                report: Optional[Dict[str, Any]]) -> None:
        if not report:
            return
        for name, rep in report["deployments"].items():
            self._shed_accum[name] = (self._shed_accum.get(name, 0)
                                      + rep["shed_delta"])

    async def _resolve_controller(self):
        """The controller's handle, looked up by name when there is none:
        before the first answer, and again after any failure (the old loop
        resolved once and then polled a dead handle forever, so a
        controller restart left every proxy blind until ITS restart)."""
        if self._controller is None:
            # get_actor is a blocking driver-style call — it must run on
            # an executor thread, never on this event loop (it would
            # deadlock the proxy's accept loop).
            self._controller = await asyncio.get_running_loop(
            ).run_in_executor(
                None, lambda: ray_tpu.get_actor(CONTROLLER_NAME))
        return self._controller

    async def _route_refresh_loop(self) -> None:
        while True:
            try:
                controller = await self._resolve_controller()
                report = self._take_ingress_report()
                try:
                    routing = await controller.get_routing.remote(
                        self._version, report)
                except Exception:
                    self._restore_ingress_report(report)
                    raise
                self._apply_routing(routing)
            except Exception:
                if self._controller is not None:
                    logger.warning("route refresh failed; will re-resolve "
                                   "controller", exc_info=True)
                self._controller = None
            await asyncio.sleep(1.0)

    def _apply_routing(self, routing) -> None:
        from ray_tpu.serve._handle import DeploymentHandle

        if routing is None:
            return
        self._version = routing["version"]
        self._deployments = routing["deployments"]
        routes = {}
        for name, info in routing["deployments"].items():
            prefix = info.get("route_prefix")
            if prefix:
                routes[prefix] = name
                if name not in self._handles:
                    self._handles[name] = DeploymentHandle(name)
        self._routes = routes
        # Readiness = the route table has loaded at least once, even if it
        # is empty: the proxy is no longer blind to the controller.
        self._ready = True

    async def _force_refresh(self) -> None:
        # A request can arrive before the refresh loop has found the
        # controller (a proxy just started, on a loaded machine): look for
        # it here too, or a route that `serve.run` has returned for is
        # answered 404.
        try:
            controller = await self._resolve_controller()
        except Exception:
            return  # none to ask: the refresh loop keeps looking
        try:
            self._apply_routing(await controller.get_routing.remote(-1))
        except Exception:
            logger.exception("forced route refresh failed")

    # ------------------------------------------------------------------
    async def _on_conn(self, reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                line = await reader.readline()
                if not line or line == b"\r\n":
                    return
                try:
                    method, path, _ = line.decode().split(" ", 2)
                except ValueError:
                    return
                headers: Dict[str, str] = {}
                header_bytes = len(line)
                overflow = False
                while True:
                    h = await reader.readline()
                    if h in (b"\r\n", b"", b"\n"):
                        break
                    header_bytes += len(h)
                    if (len(headers) >= MAX_HEADER_COUNT
                            or header_bytes > self._max_header_bytes):
                        # Keep consuming to the blank line so the 431 can
                        # go out on a valid HTTP exchange, but parse no
                        # more — bounded by the stream's own readline cap.
                        overflow = True
                        continue
                    k, _, v = h.decode().partition(":")
                    headers[k.strip().lower()] = v.strip()
                if overflow:
                    self._shed("-", "headers_too_large")
                    await self._respond(writer, 431,
                                        b"header block too large",
                                        close=True)
                    return
                try:
                    n = int(headers.get("content-length", 0) or 0)
                except ValueError:
                    await self._respond(writer, 400,
                                        b"bad content-length", close=True)
                    return
                if n < 0 or n > self._max_body:
                    # Reject on the DECLARED size — the body is never read,
                    # so the connection cannot be reused: close it.
                    self._shed("-", "body_too_large")
                    await self._respond(writer, 413,
                                        b"body too large", close=True)
                    return
                body = b""
                if n:
                    body = await reader.readexactly(n)
                keep = await self._dispatch(method, path, headers, body,
                                            writer)
                if not keep:
                    return
        except (ConnectionError, asyncio.IncompleteReadError,
                asyncio.LimitOverrunError, ValueError):
            # ValueError/LimitOverrunError: a single line (request line or
            # header) blew past the StreamReader's 64 KiB limit.
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    def _match(self, path: str):
        best = None
        for prefix, name in self._routes.items():
            if path == prefix or path.startswith(
                    prefix.rstrip("/") + "/") or prefix == "/":
                if best is None or len(prefix) > len(best[0]):
                    best = (prefix, name)
        return best

    def _shed(self, deployment: str, reason: str) -> None:
        self._m_shed.inc(tags={"deployment": deployment, "reason": reason})
        if deployment != "-":
            # "-" sheds (unrouted / malformed) have no deployment to
            # scale; everything else feeds the autoscaling signal.
            self._shed_accum[deployment] = (
                self._shed_accum.get(deployment, 0) + 1)

    def _timeout_for(self, name: str) -> float:
        info = self._deployments.get(name) or {}
        try:
            return float(info.get("request_timeout_s",
                                  self._default_timeout_s))
        except (TypeError, ValueError):
            return self._default_timeout_s

    async def _dispatch(self, method: str, path: str, headers: Dict[str, str],
                        body: bytes, writer: asyncio.StreamWriter) -> bool:
        if path == "/-/healthz":
            # Liveness: the process is up and serving its event loop.
            await self._respond(writer, 200, b"ok")
            return True
        if path == "/-/ready":
            # Readiness: routes fetched from the controller and not
            # draining — the gate a load balancer should use.
            if self._ready and not self._draining:
                await self._respond(writer, 200, b"ready")
            else:
                await self._respond(writer, 503,
                                    b"draining" if self._draining
                                    else b"route table not loaded",
                                    extra=_RETRY_AFTER)
            return True
        if self._draining:
            self._shed("-", "draining")
            await self._respond(writer, 503, b"proxy draining",
                                extra=_RETRY_AFTER, close=True)
            return False
        match = self._match(path)
        if match is None:
            # The periodic refresh may lag a just-deployed app — check the
            # controller once before 404ing.
            await self._force_refresh()
            match = self._match(path)
        if match is None:
            await self._respond(writer, 404, b"no route")
            return True
        prefix, name = match
        # Admission ceiling: shed at the door instead of queueing
        # unboundedly in the handle plane (SEDA: goodput collapses exactly
        # at peak when every stage accepts blindly).
        if self._ongoing >= self._max_concurrent:
            self._shed(name, "proxy_capacity")
            await self._respond(writer, 429, b"proxy at capacity",
                                extra=_RETRY_AFTER)
            return True
        # Fail fast when the deployment is known to have zero healthy
        # replicas — no point burning the request timeout to learn it.
        info = self._deployments.get(name)
        if info is not None and not info.get("replicas"):
            await self._force_refresh()
            info = self._deployments.get(name)
            if info is not None and not info.get("replicas"):
                self._shed(name, "no_replica")
                await self._respond(writer, 503, b"no healthy replicas",
                                    extra=_RETRY_AFTER)
                return True
        self._ongoing += 1
        try:
            return await self._dispatch_inner(
                method, path, headers, body, writer, prefix, name)
        finally:
            self._ongoing -= 1

    async def _dispatch_inner(self, method: str, path: str,
                              headers: Dict[str, str], body: bytes,
                              writer: asyncio.StreamWriter,
                              prefix: str, name: str) -> bool:
        acct = RequestAccount(headers.get("x-request-id", ""), False,
                              self._streams)
        timeout_s = self._timeout_for(name)
        payload: Any = None
        if body:
            try:
                payload = json.loads(body)
            except Exception:
                payload = body.decode(errors="replace")
        request = {
            "method": method,
            "path": path,
            "suffix": path[len(prefix.rstrip("/")):] or "/",
            "body": payload,
            "headers": headers,
        }
        # Streaming: the x-serve-stream header, or OpenAI-style
        # {"stream": true} in a JSON body.
        stream = acct.stream = (
            headers.get("x-serve-stream", "").lower() in ("1", "true")
            or (isinstance(payload, dict) and payload.get("stream") is True))
        handle = self._handles[name].options(stream=stream)._for_request(
            acct.rid, acct.t_read)
        loop = asyncio.get_running_loop()
        if stream:
            self._streams += 1
        try:
            if stream:
                gen = await loop.run_in_executor(None, handle.remote,
                                                 request)
                it = iter(gen)
                _END = object()

                def _next():
                    try:
                        item = next(it)
                    except StopIteration:
                        return _END
                    acct.took_item()
                    return item

                # Peek the first item: a {"__http__": {...}} envelope lets
                # the deployment pick the response content-type (SSE for
                # OpenAI-compatible endpoints). The peek also absorbs any
                # backpressure retry BEFORE the 200 status line commits.
                first = await asyncio.wait_for(
                    loop.run_in_executor(None, acct.pooled(_next)),
                    timeout_s)
                ctype = b"application/json"
                if isinstance(first, dict) and "__http__" in first:
                    ctype = str(first["__http__"].get(
                        "content_type", "application/json")).encode()
                    first = await loop.run_in_executor(
                        None, acct.pooled(_next))
                acct.status = 200
                writer.write(
                    b"HTTP/1.1 200 OK\r\ncontent-type: " + ctype +
                    b"\r\ntransfer-encoding: chunked\r\n\r\n")
                item = first
                while item is not _END:
                    # str items go out verbatim (pre-formatted SSE frames);
                    # anything else ships as a JSON line. One executor hop
                    # per item: the generator's blocking ray.get must stay
                    # off this event loop.
                    if isinstance(item, str):
                        chunk = item.encode()
                    else:
                        chunk = (json.dumps(item, default=str) + "\n").encode()
                    t_write = time.perf_counter_ns()
                    writer.write(hex(len(chunk))[2:].encode() + b"\r\n"
                                 + chunk + b"\r\n")
                    await writer.drain()
                    acct.wrote(len(chunk), t_write)
                    item = await loop.run_in_executor(
                        None, acct.pooled(_next))
                writer.write(b"0\r\n\r\n")
                await writer.drain()
                return True
            # The wait_for is the hard hang-proofing bound: even if the
            # executor call wedges below result()'s own timeout (e.g. a
            # stuck replica pick), the client still gets its 504.
            resp = await asyncio.wait_for(
                loop.run_in_executor(
                    None, acct.pooled(
                        lambda: handle.remote(request).result(
                            timeout=timeout_s))),
                timeout_s + 5.0)
            acct.took_item()
            status = 200
            ctype = b"application/json"
            if isinstance(resp, dict) and "__http__" in resp:
                meta = resp["__http__"]
                status = int(meta.get("status", 200))
                ctype = str(meta.get(
                    "content_type", "application/json")).encode()
                resp = resp.get("body")
            data = json.dumps(resp, default=str).encode()
            acct.status = status
            t_write = time.perf_counter_ns()
            await self._respond(writer, status, data, ctype=ctype)
            acct.wrote(len(data), t_write)
            return True
        except Exception as e:
            if isinstance(e, ConnectionError) and writer.is_closing():
                acct.status = 499  # the client went away: no one to answer
                raise
            status, reason, note = _classify_error(e)
            acct.status = status
            if reason is not None:
                self._shed(name, reason)
                await self._respond(
                    writer, status, note,
                    extra=_RETRY_AFTER if status in (429, 503) else b"")
                return True
            logger.exception("request failed")
            await self._respond(writer, 500, str(e).encode())
            return True
        finally:
            if stream:
                self._streams -= 1
            acct.close(handle)

    async def _respond(self, writer, status: int, body: bytes,
                       ctype: bytes = b"text/plain", extra: bytes = b"",
                       close: bool = False) -> None:
        conn = b"close" if close else b"keep-alive"
        writer.write(b"HTTP/1.1 " + str(status).encode() +
                     b" X\r\ncontent-type: " + ctype +
                     b"\r\ncontent-length: " + str(len(body)).encode() +
                     b"\r\n" + extra +
                     b"connection: " + conn + b"\r\n\r\n" + body)
        await writer.drain()


def _classify_error(e: BaseException) -> Tuple[int, Optional[str], bytes]:
    """Map a dispatch failure to (status, shed_reason, body). shed_reason
    None = not an overload shed: log + 500 like any other bug."""
    if unwrap_backpressure(e) is not None:
        return 429, "backpressure", b"overloaded, retry later"
    if isinstance(e, (GetTimeoutError, asyncio.TimeoutError, TimeoutError)):
        return 504, "timeout", b"request timed out"
    if isinstance(e, NoHealthyReplicasError):
        return 503, "no_replica", b"no healthy replicas"
    if isinstance(e, RayActorError) or isinstance(
            getattr(e, "cause", None), RayActorError):
        return 503, "replica_died", b"replica unavailable"
    return 500, None, b""
