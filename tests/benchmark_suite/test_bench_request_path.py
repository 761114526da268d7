"""The four readers of a request's path through the runtime
(`request_inbound_mean_ms`, `stream_report_mean_ms`, `stream_held_mean_ms`,
`stream_paused_pct`) on hand-made `ray_tpu.request.arrived` and
`ray_tpu.stream.sent` marks, and their entries in the manifest. What the
program really emits is held by `tests/test_request_path.py`."""

from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO
from benchmark import manifest as mf
from benchmark import program_trace

ARRIVED, SENT = "ray_tpu.request.arrived", "ray_tpu.stream.sent"
LAYER = "runtime: proxy, router, replica actor"
BOTH = ["chat-steady", "decode-heavy"]
ENTRIES = {
    "request_inbound_mean_ms": ("ms", "ttft_p95_ms", ["chat-steady"]),
    "stream_report_mean_ms": ("ms", "out_tok_per_s", BOTH),
    "stream_held_mean_ms": ("ms", "out_tok_per_s", BOTH),
    "stream_paused_pct": ("%", "out_tok_per_s", BOTH),
}


def _sent(rid="r", items=10, body=80.0, serialize=1.0, report=15.0,
          paused=4.0, held=20.0, **more):
    return (SENT, dict(rid=rid, task="ab", items=items, bytes=900,
                       body_ms=body, serialize_ms=serialize,
                       report_ms=report, report_max_ms=3.0, paused_ms=paused,
                       unconsumed_max=2, held_ms=held, held_max_ms=5.0,
                       starved_ms=60.0, **more))


def _arrived(rid="r", pre=1.5, dispatch=0.5):
    return (ARRIVED, dict(rid=rid, pre_ms=pre, dispatch_ms=dispatch,
                          ongoing=3))


def _obs(monkeypatch, tmp_path, marks):
    """An observation whose one trace file holds `marks`, (name, stats)
    pairs, as events of a replica's handler thread."""
    events = [NS(name=name, start_ns=1e5 * i, duration_ns=0,
                 stats=list(stats.items()))
              for i, (name, stats) in enumerate(marks)]
    trace = NS(planes=[NS(name="/host:CPU", lines=[
        NS(name="ThreadPoolExecutor-0_3", events=events)])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(program_trace.xplane, "load", lambda _: trace)
    program_trace._read.cache_clear()
    return {"traces": [{"path": str(path)}]}


@pytest.fixture
def readers():
    m = mf.Manifest(REPO)
    yield {name: m.reader(name) for name in ENTRIES}
    program_trace._read.cache_clear()


FIVE = program_trace.MIN_EVENTS


@pytest.mark.parametrize("metric,marks,want", [
    ("request_inbound_mean_ms", [_arrived()] * 8, 2.0),
    ("request_inbound_mean_ms",
     [_arrived(pre=1.0, dispatch=0.25)] * 3 + [_arrived(pre=4.0,
                                                        dispatch=2.75)] * 3,
     4.0),
    # a call made on the actor itself carries no id and is no request
    ("request_inbound_mean_ms",
     [_arrived()] * FIVE + [_arrived(rid="", pre=0.0, dispatch=90.0)] * 4,
     2.0),
    ("request_inbound_mean_ms", [_arrived()] * (FIVE - 1), None),
    ("request_inbound_mean_ms", [_arrived(rid="")] * 9, None),
    ("stream_report_mean_ms", [_sent()] * 6, 1.5),
    # per item, not per stream: a long stream weighs as its items do
    ("stream_report_mean_ms",
     [_sent(items=10, report=10.0)] * 4 + [_sent(items=160, report=40.0)],
     0.4),
    ("stream_report_mean_ms",
     [_sent()] * FIVE + [_sent(rid="", items=1, report=500.0)] * 3, 1.5),
    ("stream_report_mean_ms", [_sent()] * (FIVE - 1), None),
    ("stream_report_mean_ms", [_sent(items=0, report=0.0)] * 6, None),
    ("stream_held_mean_ms", [_sent()] * 6, 2.0),
    ("stream_held_mean_ms",
     [_sent(held=0.0)] * 5 + [_sent(items=50, held=300.0)], 3.0),
    ("stream_held_mean_ms",
     [_sent()] * FIVE + [_sent(rid="", held=9e3)] * 2, 2.0),
    ("stream_held_mean_ms", [_sent()] * (FIVE - 1), None),
    ("stream_paused_pct", [_sent()] * 6, 4.0),
    ("stream_paused_pct", [_sent(paused=0.0)] * 7, 0.0),
    ("stream_paused_pct",
     [_sent(body=50.0, serialize=0.0, report=10.0, paused=40.0)] * 5
     + [_sent(body=290.0, serialize=0.0, report=10.0, paused=0.0)], 25.0),
    ("stream_paused_pct",
     [_sent()] * FIVE + [_sent(rid="", paused=1e4)], 4.0),
    ("stream_paused_pct", [_sent()] * (FIVE - 1), None),
    ("stream_paused_pct",
     [_sent(body=0.0, serialize=0.0, report=0.0, paused=0.0)] * 6, None),
])
def test_reader_on_hand_made_marks(monkeypatch, tmp_path, readers, metric,
                                   marks, want):
    got = readers[metric](_obs(monkeypatch, tmp_path, marks))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_reader_finds_nothing_without_the_mark_or_a_trace(
        monkeypatch, tmp_path, readers, metric):
    read = readers[metric]
    # the parent's program: the engine's marks alone
    others = [("ray_tpu.request.stream_done",
               dict(rid="r", tokens=8, items=3, lag_ms=4.0))] * 9
    assert read(_obs(monkeypatch, tmp_path, others)) is None
    # marks that lack what the reader reads
    bare = [(ARRIVED, dict(rid="r", ongoing=1)),
            (SENT, dict(rid="r", task="ab", items=4))] * 9
    assert read(_obs(monkeypatch, tmp_path, bare)) is None
    assert read({"traces": [{"path": "/nonexistent/x.xplane.pb"}]}) is None
    assert read({"traces": []}) is None and read({}) is None


def test_manifest_has_the_four_entries_as_the_last_of_the_list():
    m = mf.Manifest(REPO)
    assert mf.check(m) == []
    names = [x["name"] for x in m.data["per_layer"]]
    assert names[-4:] == list(ENTRIES)
    for name, (unit, moves, cells) in ENTRIES.items():
        assert m.per_layer[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": LAYER, "moves": moves,
            "workloads": cells}
        for cell in cells:
            assert name in {x["name"]
                            for x in m.metrics_for(cell, "per_layer")}
            assert moves in {x["name"]
                             for x in m.metrics_for(cell, "end_to_end")}
    # the layer's name as the benchmark already has it, letter for letter,
    # and what stood for the runtime before stays
    for old in ("route_rtt_p50_ms", "stream_lag_mean_ms",
                "stream_tokens_per_item"):
        assert m.per_layer[old]["layer"] == LAYER
    # the cells whose per-layer sets other tests of the benchmark hold
    for cell in m.cells:
        if cell not in BOTH:
            assert not set(ENTRIES) & {
                x["name"] for x in m.metrics_for(cell, "per_layer")}
