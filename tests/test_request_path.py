"""A request's path through the runtime, measured from inside: one `rid`
from the proxy to the engine and back (`ray_tpu.proxy.request`,
`ray_tpu.request.arrived`, `.first_token`, `.finished`, `.stream_done`,
`ray_tpu.stream.sent`), the stream's time split where it goes, and the
owner's account of it carried in the producer's mark. Real proxy, handle,
replica and engine (the tiny model), on the CPU; one cluster for the file."""

import asyncio
import concurrent.futures
import functools
import json
import signal
import socket
import time
from types import SimpleNamespace as NS

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import flight_recorder as fr
from ray_tpu._private import serialization as ser
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.ids import TaskID
from ray_tpu.util import state

TINY = {"model": "tiny", "model_config": {"vocab_size": 300},
        "engine_config": {"max_seqs": 4, "page_size": 4,
                          "max_pages_per_seq": 32},
        "model_id": "tiny-test-model"}
DEPLOYMENT = "OpenAI:tiny"
REQUEST_MARKS = ("ray_tpu.request.arrived", "ray_tpu.request.first_token",
                 "ray_tpu.request.finished", "ray_tpu.request.stream_done",
                 "ray_tpu.stream.sent")
STREAM_PARTS = ("body_ms", "serialize_ms", "report_ms", "paused_ms")


def within(seconds):
    """The test's own time limit, inside the plugin's: a wait that does not
    end fails this test soon, and leaves the file's cluster to the rest."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            def late(signum, frame):
                raise TimeoutError(f"{fn.__name__} took over {seconds} s")

            before = signal.signal(signal.SIGALRM, late)
            signal.alarm(seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, before)
        return run
    return wrap


@pytest.fixture(scope="module")
def app(ray_cluster):
    from ray_tpu.llm import build_openai_app

    handle = serve.run(build_openai_app(TINY), route_prefix="/v1")
    port = serve.http_port()
    deadline = time.monotonic() + 120  # the replica loads its weights
    while _post(port, {"prompt": "warm", "max_tokens": 2})[0] != 200:
        assert time.monotonic() < deadline
        time.sleep(0.5)
    yield NS(handle=handle, port=port)
    serve.shutdown()


def _post(port, body, headers=None):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps(body),
                 headers={"content-type": "application/json",
                          **(headers or {})})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _marks(events, name, **args):
    """Arguments of the marks called `name` whose arguments include `args`,
    among ring events."""
    return [e["args"] for e in events
            if e.get("kind") == "span" and e["name"] == name
            and all(e["args"].get(k) == v for k, v in args.items())]


def _cluster_events():
    """Every worker's ring (the proxy's and the replica's among them)."""
    return [e for node in state.flight_record()["nodes"].values()
            for w in node["workers"].values() for e in w["events"]]


def _wait_marks(names, deadline_s=30.0, **args):
    """{name: the one mark of that name with `args`}, once all are there."""
    deadline = time.monotonic() + deadline_s
    while True:
        events = _cluster_events()
        found = {n: _marks(events, n, **args) for n in names}
        if all(found.values()):
            assert all(len(v) == 1 for v in found.values()), found
            return {n: v[0] for n, v in found.items()}
        assert time.monotonic() < deadline, (
            f"no {[n for n, v in found.items() if not v]} with {args}")
        time.sleep(0.2)


# -- one id from the socket to the engine and back -------------------------
@pytest.mark.parametrize("given", ["", "client-made-id.7"])
@within(120)
def test_one_rid_joins_every_mark_of_a_streamed_http_request(app, given):
    before = {m["rid"] for m in _marks(_cluster_events(),
                                       "ray_tpu.proxy.request")}
    status, data = _post(app.port, {"prompt": "hello", "max_tokens": 24,
                                    "stream": True},
                         {"x-request-id": given} if given else None)
    assert status == 200 and data.endswith(b"data: [DONE]\n\n")
    if given:
        rid = given
    else:
        deadline = time.monotonic() + 30
        while not (new := {m["rid"] for m in _marks(
                _cluster_events(), "ray_tpu.proxy.request", stream=True)}
                - before):
            assert time.monotonic() < deadline
            time.sleep(0.2)
        (rid,) = new
        assert len(rid) == 12 and int(rid, 16) >= 0
    got = _wait_marks(("ray_tpu.proxy.request",) + REQUEST_MARKS, rid=rid)

    proxy = got["ray_tpu.proxy.request"]
    assert proxy["status"] == 200 and proxy["stream"] is True
    # header, one item an engine step that made a frame, closing frames
    done, sent = got["ray_tpu.request.stream_done"], got["ray_tpu.stream.sent"]
    assert proxy["items"] == sent["items"] == done["items"] >= 3
    assert done["tokens"] == got["ray_tpu.request.finished"]["tokens"] == 24
    assert proxy["bytes"] == len(data)  # the body, without its framing
    assert 0 < proxy["pre_ms"] <= proxy["first_item_ms"] <= proxy["total_ms"]
    assert 0 <= proxy["pool_wait_max_ms"] <= proxy["pool_wait_ms"]
    assert (proxy["pool_wait_ms"] + proxy["next_ms"] + proxy["write_ms"]
            <= proxy["total_ms"])
    assert proxy["open_streams"] == 0

    arrived = got["ray_tpu.request.arrived"]
    # the proxy's own account of the way in, carried, not recomputed
    assert arrived["pre_ms"] == proxy["pre_ms"]
    assert 0 < arrived["dispatch_ms"] < 5e3 and arrived["ongoing"] == 0
    first = got["ray_tpu.request.first_token"]
    assert first["queue_ms"] >= 0 and first["prefill_ms"] > 0
    assert sent["task"] and sent["bytes"] > 0 and sent["paused_ms"] == 0


@within(120)
def test_one_rid_joins_the_marks_of_a_unary_http_request(app):
    status, _ = _post(app.port, {"prompt": "hello", "max_tokens": 5},
                      {"x-request-id": "unary-1"})
    assert status == 200
    got = _wait_marks(("ray_tpu.proxy.request", "ray_tpu.request.arrived",
                       "ray_tpu.request.first_token",
                       "ray_tpu.request.finished"), rid="unary-1")
    proxy = got["ray_tpu.proxy.request"]
    assert proxy["stream"] is False and proxy["items"] == 1
    assert proxy["status"] == 200 and proxy["bytes"] > 0
    assert got["ray_tpu.request.finished"]["tokens"] == 5
    # no stream: neither of the stream's marks
    events = _cluster_events()
    assert not _marks(events, "ray_tpu.stream.sent", rid="unary-1")
    assert not _marks(events, "ray_tpu.request.stream_done", rid="unary-1")


@within(120)
def test_one_rid_over_a_bare_handle(app):
    request = {"method": "POST", "path": "/v1/completions",
               "suffix": "/completions", "headers": {},
               "body": {"prompt": "hello", "max_tokens": 9, "stream": True}}
    gen = app.handle.options(stream=True).remote(request)
    rid = gen.request_id
    assert len(rid) == 12
    items = list(gen)
    assert items[0] == {"__http__": {"content_type": "text/event-stream"}}
    assert items[-1].endswith("data: [DONE]\n\n")
    got = _wait_marks(REQUEST_MARKS, rid=rid)
    assert got["ray_tpu.stream.sent"]["items"] == len(items)
    assert got["ray_tpu.request.finished"]["tokens"] == 9
    assert got["ray_tpu.request.arrived"]["pre_ms"] > 0
    # no proxy on this path
    assert not _marks(_cluster_events(), "ray_tpu.proxy.request", rid=rid)

    # a second request of the handle is another request
    resp = app.handle.remote(dict(request, body={"prompt": "hello",
                                                 "max_tokens": 3}))
    assert resp.request_id != rid and len(resp.request_id) == 12
    assert resp.result(timeout=60)["usage"]["completion_tokens"] == 3
    _wait_marks(("ray_tpu.request.arrived", "ray_tpu.request.finished"),
                rid=resp.request_id)


@within(120)
def test_two_requests_under_one_id_do_not_share_a_queue(app):
    """A client may send its id twice at once (a retry): the engine's
    second request gets a suffix, and both streams end whole."""
    body = {"prompt": "hello", "max_tokens": 40, "stream": True}
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        a, b = [pool.submit(_post, app.port, body, {"x-request-id": "twice"})
                for _ in range(2)]
        for status, data in (a.result(), b.result()):
            assert status == 200 and data.endswith(b"data: [DONE]\n\n")
    deadline = time.monotonic() + 30
    while len(done := [m for m in _marks(_cluster_events(),
                                         "ray_tpu.request.finished")
                       if m["rid"].startswith("twice")]) < 2:
        assert time.monotonic() < deadline
        time.sleep(0.2)
    assert [m["tokens"] for m in done] == [40, 40]


@within(120)
def test_a_stream_the_client_abandons_still_leaves_its_marks(app):
    body = json.dumps({"prompt": "hello", "max_tokens": 30,
                       "stream": True}).encode()
    sock = socket.create_connection(("127.0.0.1", app.port), timeout=60)
    sock.sendall(b"POST /v1/completions HTTP/1.1\r\nhost: x\r\n"
                 b"x-request-id: gone-1\r\ncontent-type: application/json\r\n"
                 b"content-length: " + str(len(body)).encode() + b"\r\n\r\n"
                 + body)
    assert sock.recv(12) == b"HTTP/1.1 200"
    # gone with the stream open: the proxy's next write is answered RST
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    b"\x01\x00\x00\x00\x00\x00\x00\x00")
    sock.close()
    got = _wait_marks(("ray_tpu.proxy.request",) + REQUEST_MARKS,
                      rid="gone-1")
    proxy, sent = got["ray_tpu.proxy.request"], got["ray_tpu.stream.sent"]
    # under the producer's pause (16 unconsumed), so it ran to its end
    assert got["ray_tpu.request.finished"]["tokens"] == 30
    assert proxy["status"] in (499, 200) and proxy["items"] <= sent["items"]
    if proxy["status"] == 499:
        assert proxy["items"] < sent["items"]
    # and the replica serves the next request
    assert _post(app.port, {"prompt": "next", "max_tokens": 2})[0] == 200


# -- the proxy's pool: what S3 has to tell apart ---------------------------
class _Sink:
    """The writer side of a client's connection, as `_dispatch_inner` uses
    it."""

    def __init__(self):
        self.data = b""

    def write(self, data):
        self.data += data

    async def drain(self):
        pass

    def is_closing(self):
        return False


def _streams_through_a_pool_of(threads, streams, tag):
    """`streams` streamed requests at once through a proxy's dispatch in
    this process, on a loop whose default executor has `threads` threads;
    their `ray_tpu.proxy.request` marks (this process's ring)."""
    from ray_tpu.serve._handle import DeploymentHandle
    from ray_tpu.serve._proxy import ProxyActor

    proxy = ProxyActor()
    proxy._handles[DEPLOYMENT] = DeploymentHandle(DEPLOYMENT)
    body = json.dumps({"prompt": "hello", "max_tokens": 100,
                       "stream": True}).encode()
    sinks = [_Sink() for _ in range(streams)]
    loop = asyncio.new_event_loop()
    pool = concurrent.futures.ThreadPoolExecutor(threads)
    loop.set_default_executor(pool)

    async def all_at_once():
        return await asyncio.gather(*[
            proxy._dispatch_inner(
                "POST", "/v1/completions", {"x-request-id": f"{tag}-{i}"},
                body, sink, "/v1", DEPLOYMENT)
            for i, sink in enumerate(sinks)])

    try:
        kept = loop.run_until_complete(all_at_once())
    finally:
        pool.shutdown(wait=True)
        loop.close()
    assert all(kept) and proxy._streams == 0
    assert all(s.data.endswith(b"data: [DONE]\n\n\r\n0\r\n\r\n")
               for s in sinks)
    marks = [m for m in _marks(fr.dump_events(), "ray_tpu.proxy.request")
             if m["rid"].startswith(tag + "-")]
    assert len(marks) == streams and all(m["status"] == 200 for m in marks)
    return marks


@within(200)
def test_pool_wait_grows_when_streams_outnumber_the_pools_threads(app):
    roomy = _streams_through_a_pool_of(8, 4, "roomy")
    tight = _streams_through_a_pool_of(2, 4, "tight")
    assert sorted(m["open_streams"] for m in tight) == [0, 1, 2, 3]
    # with a thread each a call waits for the hand-over alone; with two
    # threads for four streams, two of the four always wait behind a stream
    # that waits for its engine: half of the streams' time by arithmetic
    def share(marks):
        return (sum(m["pool_wait_ms"] for m in marks)
                / sum(m["total_ms"] for m in marks))

    assert share(tight) > 0.25 and share(tight) > 3 * share(roomy)
    assert (max(m["pool_wait_max_ms"] for m in tight)
            > 3 * max(m["pool_wait_max_ms"] for m in roomy))
    # what a stream's items cost the proxy, apart from waiting for a thread
    for m in tight:
        assert m["items"] >= 8 and m["bytes"] > 0 and m["write_ms"] >= 0
        assert (m["pool_wait_ms"] + m["next_ms"] + m["write_ms"]
                <= m["total_ms"])
    # the replica's side of the same requests: all four ran at once
    sent = [_wait_marks(("ray_tpu.stream.sent",), rid=f"tight-{i}")
            for i in range(4)]
    assert all(s["ray_tpu.stream.sent"]["items"] == m["items"]
               for s, m in zip(sent, sorted(tight, key=lambda m: m["rid"])))


# -- the stream itself: a plain dynamic task, no serve ---------------------
@ray_tpu.remote
class Producer:
    def ready(self):
        return True

    def stream(self, n, sleep_s):
        """Times itself, first line to last: the stream's length as its
        own thread saw it."""
        t0 = time.perf_counter()
        try:
            for i in range(n):
                if sleep_s:
                    time.sleep(sleep_s)
                yield i
        finally:
            self.length_ms = (time.perf_counter() - t0) * 1e3

    def length(self):
        return self.length_ms


def _stream(producer, n, producer_sleep_s, consumer_sleep_s, first_wait_s=0):
    """Run one stream of `n` items; its `ray_tpu.stream.sent` mark and the
    owner's final account."""
    gen = producer.stream.options(num_returns="dynamic").remote(
        n, producer_sleep_s)
    time.sleep(first_wait_s)
    got = []
    for ref in gen:
        got.append(ray_tpu.get(ref))
        time.sleep(consumer_sleep_s)
    assert got == list(range(n))
    task = gen._task_id
    sent = _wait_marks(("ray_tpu.stream.sent",),
                       task=task.hex())["ray_tpu.stream.sent"]
    assert sent["rid"] == "" and sent["items"] == n
    return sent, worker_mod.global_worker()._generators[task]


@within(120)
def test_the_four_parts_sum_to_the_streams_length(ray_cluster):
    p = Producer.remote()
    assert ray_tpu.get(p.ready.remote())
    sent, _ = _stream(p, 25, 0.04, 0)
    length = ray_tpu.get(p.length.remote())
    assert length > 25 * 40
    assert sum(sent[k] for k in STREAM_PARTS) == pytest.approx(length,
                                                               rel=0.01)
    # where it went: the generator's own sleeps, then the round trips
    assert sent["body_ms"] > 25 * 40 > 10 * sent["report_ms"] > 0
    assert 0 < sent["report_max_ms"] <= sent["report_ms"]
    assert sent["serialize_ms"] > 0 and sent["paused_ms"] == 0


@within(120)
def test_a_late_consumer_shows_as_held_and_a_late_producer_as_starved(
        ray_cluster):
    p = Producer.remote()
    assert ray_tpu.get(p.ready.remote())
    # the producer is done at once; the consumer comes late and dawdles:
    # items lie in the owner, and past 16 of them the producer pauses
    sent, st = _stream(p, 40, 0, 0.01, first_wait_s=0.3)
    assert sent["held_ms"] > 1000 and sent["held_max_ms"] > 250
    assert sent["starved_ms"] < sent["held_ms"] / 20
    assert sent["unconsumed_max"] >= 17 and sent["paused_ms"] > 100
    # the mark is the owner's account as of its last reply; the owner's
    # own goes on to the last item taken
    assert st.held_ns / 1e6 >= sent["held_ms"] and not st.landed
    assert st.held_max_ns / 1e6 >= sent["held_max_ms"]

    # the producer sleeps before every item; the consumer always waits
    sent, st = _stream(p, 15, 0.03, 0)
    assert sent["starved_ms"] > 15 * 30 * 0.8
    assert sent["held_ms"] < sent["starved_ms"] / 4
    assert sent["unconsumed_max"] <= 2 and sent["paused_ms"] == 0
    assert st.starved_ns / 1e6 >= sent["starved_ms"] and not st.landed


# -- the recorder off ------------------------------------------------------
@pytest.fixture
def recorder_off():
    fr.set_enabled(False)
    yield
    fr.set_enabled(True)


def _report(w, task, index):
    obj = ser.serialize(index)
    return w.loop_thread.run(w._rpc_report_generator_item(
        task_id=task.binary(), index=index,
        item=("inline", obj.metadata, ser.wire_buffers(obj.buffers))))


@within(60)
def test_recorder_off_leaves_the_reply_and_the_paths_as_they_were(
        ray_cluster, recorder_off):
    w = worker_mod.global_worker()
    # the owner: the reply is what it was, and nothing is stamped
    task = TaskID.from_random()
    assert _report(w, task, 0) == {"unconsumed": 1}
    assert _report(w, task, 1) == {"unconsumed": 2}
    assert w.loop_thread.run(w.gen_next(task, 0)) is not None
    st = w._generators[task]
    assert not st.landed and (st.held_ns, st.starved_ns) == (0, 0)
    assert _report(w, task, 2) == {"unconsumed": 2}
    # the producer: the shared no-op, no clock read, no mark
    assert fr.laps("body", "report") is fr._NO_LAPS
    assert fr._NO_LAPS.lap("body") == 0 and fr._NO_LAPS.ms() == {}
    ring = len(fr.dump_events())
    spec = NS(owner_address=w.address, task_id=TaskID.from_random())
    assert w._stream_generator(spec, iter("abc")) == {
        "results": [], "generator_count": 3}
    assert len(fr.dump_events()) == ring
    assert [ray_tpu.get(ray_tpu.ObjectRef(oid, owner_address=w.address))
            for oid in (w.loop_thread.run(w.gen_next(spec.task_id, i))
                        for i in range(3))] == ["a", "b", "c"]

    # and on again: the same calls state the account
    fr.set_enabled(True)
    task = TaskID.from_random()
    assert set(_report(w, task, 0)) == {"unconsumed", "held_ns",
                                        "held_max_ns", "starved_ns"}
    spec = NS(owner_address=w.address, task_id=TaskID.from_random())
    assert w._stream_generator(spec, iter("abc"))["generator_count"] == 3
    (sent,) = _marks(fr.dump_events(), "ray_tpu.stream.sent",
                     task=spec.task_id.hex())
    assert sent["items"] == 3 and sent["rid"] == ""
    assert set(STREAM_PARTS) <= set(sent)
