"""The benchmark's client: HTTP/1.1 over asyncio streams, one connection
per request, one thread for all of them. SSE frames arrive as chunks of a
chunked response, one frame per chunk (`serve/_proxy.py`); every frame is
stamped when it is read. Also the few cluster helpers copied from
`chip_smoke.py` (readiness poll, replica lookup, waiting for the chip's
holder to be gone): the benchmark imports nothing from that script."""

from __future__ import annotations

import asyncio
import json
import os
import time
from typing import Any, Callable, Dict, List, Tuple

from benchmark.loadgen import Req
from benchmark.metrics import Rec

HOST = "127.0.0.1"


async def _read_head(reader: asyncio.StreamReader) -> Tuple[int, Dict[str, str]]:
    line = await reader.readline()
    parts = line.decode("latin-1").split(" ", 2)
    if len(parts) < 2:
        raise ConnectionError(f"bad status line {line!r}")
    status = int(parts[1])
    headers: Dict[str, str] = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers


async def _chunks(reader: asyncio.StreamReader):
    """Chunks of a chunked body, as they arrive."""
    while True:
        size = int((await reader.readline()).split(b";")[0].strip() or b"0",
                   16)
        if size == 0:
            await reader.readline()
            return
        data = await reader.readexactly(size)
        await reader.readexactly(2)
        yield data


async def _request(port: int, method: str, path: str, body: Any = None):
    reader, writer = await asyncio.open_connection(HOST, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write(
        f"{method} {path} HTTP/1.1\r\nhost: {HOST}\r\n"
        f"content-type: application/json\r\n"
        f"content-length: {len(payload)}\r\n\r\n".encode() + payload)
    await writer.drain()
    return reader, writer


async def get(port: int, path: str) -> Tuple[int, bytes]:
    reader, writer = await _request(port, "GET", path)
    try:
        status, headers = await _read_head(reader)
        if headers.get("transfer-encoding") == "chunked":
            data = b"".join([c async for c in _chunks(reader)])
        else:
            data = await reader.readexactly(
                int(headers.get("content-length", 0)))
        return status, data
    finally:
        writer.close()


async def stream_completion(port: int, req: Req, rec: Rec,
                            clock: Callable[[], float],
                            temperature: float = 0.0) -> None:
    """POST /v1/completions with a pre-tokenized prompt, stream on; fill
    `rec` with the time of every frame that carried text."""
    writer = None
    try:
        rec.sent_s = clock()
        reader, writer = await _request(port, "POST", "/v1/completions", {
            "prompt": req.prompt, "max_tokens": req.max_tokens,
            "temperature": temperature, "stream": True})
        status, headers = await _read_head(reader)
        if status != 200 or "text/event-stream" not in headers.get(
                "content-type", ""):
            rec.error = f"HTTP {status} {headers.get('content-type')}"
            return
        async for chunk in _chunks(reader):
            now = clock()
            for frame in chunk.split(b"\n\n"):
                if not frame.startswith(b"data: "):
                    continue
                data = frame[6:]
                if data == b"[DONE]":
                    rec.done_s = now
                    continue
                choice = json.loads(data)["choices"][0]
                text = choice.get("text") or ""
                if text:
                    rec.events_s.append(now)
                    rec.replaced += text.count("\ufffd")
                if choice.get("finish_reason"):
                    rec.finish = choice["finish_reason"]
        if rec.done_s is None:
            rec.error = "stream ended without [DONE]"
    except (OSError, asyncio.IncompleteReadError, ValueError, KeyError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def probe_rtt(port: int, clock: Callable[[], float], stop: asyncio.Event,
                    out: List[float], period_s: float = 1.0) -> None:
    """GET /v1/models once a period: proxy, router and replica actor, but
    not the engine."""
    while not stop.is_set():
        t0 = clock()
        try:
            status, _ = await get(port, "/v1/models")
            if status == 200:
                out.append((clock() - t0) * 1e3)
        except (OSError, asyncio.IncompleteReadError, ValueError):
            pass
        try:
            await asyncio.wait_for(stop.wait(), period_s)
        except asyncio.TimeoutError:
            pass


async def open_loop(port: int, reqs: List[Req], window_s: float,
                    clock: Callable[[], float], temperature: float,
                    drain_s: float) -> List[Rec]:
    """Send each request at its due time, whatever the server does; then
    wait (at most `drain_s`) for those in flight."""
    pairs = sorted(((r, Rec(r.index, float(r.due_s),
                            want_tokens=r.max_tokens)) for r in reqs),
                   key=lambda p: p[0].due_s)
    tasks = {}
    for r, rec in pairs:
        delay = r.due_s - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks[asyncio.ensure_future(
            stream_completion(port, r, rec, clock, temperature))] = rec
    left = max(0.0, window_s - clock())
    _, pending = await asyncio.wait(list(tasks), timeout=left + drain_s)
    for t in pending:
        t.cancel()
        tasks[t].error = tasks[t].error or \
            "not finished when the drain ended"
    await asyncio.gather(*tasks, return_exceptions=True)
    return [rec for _, rec in pairs]


async def closed_loop(port: int, reqs: List[Req], clients: int,
                      window_s: float, clock: Callable[[], float],
                      temperature: float, drain_s: float) -> List[Rec]:
    """`clients` callers, each sending its next request when the reply to
    the last has ended; none starts a request after the window. Those in
    flight then run to their end (the token count is compared at rest)."""
    recs: List[Rec] = []
    cursor = [0]

    async def caller() -> None:
        while clock() < window_s:
            r = reqs[cursor[0] % len(reqs)]
            cursor[0] += 1
            rec = Rec(len(recs), clock(), want_tokens=r.max_tokens)
            recs.append(rec)
            await stream_completion(port, r, rec, clock, temperature)
            if rec.error is not None:
                await asyncio.sleep(0.05)

    tasks = [asyncio.ensure_future(caller()) for _ in range(clients)]
    _, pending = await asyncio.wait(tasks, timeout=window_s + drain_s)
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    if pending:
        for rec in recs:
            if rec.done_s is None and rec.error is None:
                rec.error = "not finished when the drain ended"
    return recs


# -- cluster helpers (copied from chip_smoke.py, PR 21) ---------------------
def wait_ready(port: int, deadline_s: float = 300.0) -> float:
    """Poll GET /v1/models until the replica (weights loaded) answers:
    `serve.run` returns before a TPU replica has loaded its weights."""
    t0 = time.monotonic()
    last: Any = None
    while time.monotonic() - t0 < deadline_s:
        try:
            status, _ = asyncio.run(asyncio.wait_for(
                get(port, "/v1/models"), 120.0))
            if status == 200:
                return time.monotonic() - t0
            last = status
        except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError,
                ValueError) as e:
            last = e
        time.sleep(0.5)
    raise RuntimeError(f"replica not ready after {deadline_s:.0f}s: {last!r}")


def replica_actors() -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.serve._controller import REPLICA_NAME_PREFIX
    from ray_tpu.util import state

    return {a["name"]: ray_tpu.get_actor(a["name"])
            for a in state.list_actors(state="ALIVE")
            if (a.get("name") or "").startswith(REPLICA_NAME_PREFIX)}


def wait_gone(pids: List[int], deadline_s: float = 120.0) -> float:
    """The chip is free only when its holder's process is gone."""
    t0 = time.monotonic()
    while any(os.path.exists(f"/proc/{p}") for p in pids):
        if time.monotonic() - t0 > deadline_s:
            raise RuntimeError(
                f"chip holder(s) {pids} still alive {deadline_s:.0f}s after "
                "shutdown: the chip is not released")
        time.sleep(0.2)
    return time.monotonic() - t0
