"""The two readers of the program's `ray_tpu.request.stream_done` mark
(`stream_lag_mean_ms`, `stream_tokens_per_item`), in the pattern of
test_bench_program_trace.py: on a hand-made trace whose marks come from
handler threads, not the `llm-engine` line, and against the manifest."""

from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO
from benchmark import manifest as mf
from benchmark import program_trace

LAYER = "runtime: proxy, router, replica actor"
CELLS = ["chat-steady", "decode-heavy"]
STREAM_METRICS = {"stream_lag_mean_ms": ("ms", "lower", "program_span"),
                  "stream_tokens_per_item": ("count", "higher",
                                             "program_counter")}


def _ev(name, start_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=0,
              stats=list(stats.items()))


def _trace(n=6):
    """`n` streamed requests that end on `n` handler threads: request i made
    64 + 8 i tokens in 10 + i items and its last item left 2 + i ms after
    the engine's hand-over; one more ended on a stop string (no `lag_ms`),
    and a mark of another name must not count."""
    lines = [NS(name="llm-engine", events=[
        _ev("bench.window", 0),
        _ev("ray_tpu.request.finished", 5, rid="r0", slot=0, tokens=64,
            decode_ms=700.0)])]
    for i in range(n):
        tokens, items = 64 + 8 * i, 10 + i
        lines.append(NS(name=f"handler-{i}", events=[
            _ev("ray_tpu.request.stream_done", 100 * i, rid=f"r{i}",
                tokens=tokens, items=items, tokens_per_item=tokens / items,
                lag_ms=2.0 + i)]))
    lines.append(NS(name="handler-stop", events=[
        _ev("ray_tpu.request.stream_done", 990, rid="rs", tokens=12,
            items=4, tokens_per_item=3.0)] if n else []))
    return NS(planes=[NS(name="/host:CPU", lines=lines),
                      NS(name="/device:TPU:0", lines=[]),
                      NS(name="Task Environment", lines=[])])


@pytest.fixture
def obs(monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    traces = {str(path): _trace()}
    monkeypatch.setattr(program_trace.xplane, "load", traces.__getitem__)
    program_trace._read.cache_clear()
    yield {"traces": [{"path": str(path), "window_s": 0.001}],
           "rewrite": lambda n: (traces.__setitem__(str(path), _trace(n)),
                                 program_trace._read.cache_clear())}
    program_trace._read.cache_clear()


@pytest.mark.parametrize("metric,want", [
    ("stream_lag_mean_ms", 4.5),               # mean of 2 .. 7
    ("stream_tokens_per_item",                 # the stop-string one counts
     (sum((64 + 8 * i) / (10 + i) for i in range(6)) + 3.0) / 7),
])
def test_reader_on_a_hand_made_trace(obs, metric, want):
    read = mf.Manifest(REPO).reader(metric)
    assert read(obs) == pytest.approx(want)
    # fewer than five of its events: nothing to average
    obs["rewrite"](3)
    assert read(obs) is None
    # a program without the mark (the parent), a run without a trace
    obs["rewrite"](0)
    assert read(obs) is None
    assert read({"traces": [{"path": "/nonexistent/x.xplane.pb"}]}) is None
    assert read({"traces": []}) is None and read({}) is None


@pytest.mark.parametrize("metric", sorted(STREAM_METRICS))
def test_manifest_has_the_entry_and_its_reader(metric):
    m = mf.Manifest(REPO)
    unit, better, source = STREAM_METRICS[metric]
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"], entry["workloads"]) == (
        unit, better, source, LAYER, "out_tok_per_s", CELLS)
    assert callable(m.reader(metric))
    # the layer's name as the benchmark already has it, letter for letter
    assert m.per_layer["route_rtt_p50_ms"]["layer"] == LAYER
    for cell in CELLS:
        assert metric in {x["name"]
                          for x in m.metrics_for(cell, "per_layer")}
        assert "out_tok_per_s" in {
            x["name"] for x in m.metrics_for(cell, "end_to_end")}
    assert metric not in {x["name"]
                          for x in m.metrics_for("train-2k", "per_layer")}
