"""`decode_chained_pct`: 100 x the mean `chained` of the engine's
`ray_tpu.engine.dispatch_decode` spans (100 where the chip always had the
next window queued, 0 where the host read every window before it built the
next), on hand-made spans, in the manifest, and against what a real engine
on the CPU emits with the pipeline on and off."""

from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO
from benchmark import manifest as mf
from benchmark import program_trace

METRIC = "decode_chained_pct"
SPAN = "ray_tpu.engine.dispatch_decode"


def _obs(monkeypatch, tmp_path, chained, name=SPAN, stat="chained"):
    """An observation whose one trace file holds a `dispatch_decode` span
    for each of `chained`."""
    events = [NS(name=name, start_ns=1e5 * i, duration_ns=5e3,
                 stats=[("active", 3), ("max_seqs", 16), ("steps", 4),
                        ("free_slots", 13), (stat, c), ("across", "none")])
              for i, c in enumerate(chained)]
    trace = NS(planes=[NS(name="/host:CPU", lines=[
        NS(name="llm-engine", events=events)])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(program_trace.xplane, "load", lambda _: trace)
    program_trace._read.cache_clear()
    return {"traces": [{"path": str(path)}]}


@pytest.fixture
def read():
    yield mf.Manifest(REPO).reader(METRIC)
    program_trace._read.cache_clear()


@pytest.mark.parametrize("chained,want", [
    ([1] * 40, 100.0),                  # the chain was never broken
    ([0] * 7, 0.0),                     # `pipeline_dispatch=False`
    ([0, 1, 1, 1] * 5, 75.0),           # a drain every fourth window
    ([False, True, True, True, True], 80.0),
    ([0] + [1] * 199, 99.5),            # one busy stretch
    ([1, 1, 0], None),                  # under MIN_EVENTS: nothing to average
    ([1] * (program_trace.MIN_EVENTS - 1), None),
    ([], None),
])
def test_reader_on_hand_made_spans(monkeypatch, tmp_path, read, chained,
                                   want):
    got = read(_obs(monkeypatch, tmp_path, chained))
    assert got == (None if want is None else pytest.approx(want))


def test_reader_finds_nothing_without_the_span_or_a_trace(
        monkeypatch, tmp_path, read):
    # a program that opens another span only; a span without `chained`
    assert read(_obs(monkeypatch, tmp_path, [1] * 9,
                     name="ray_tpu.engine.step")) is None
    assert read(_obs(monkeypatch, tmp_path, [1] * 9, stat="linked")) is None
    assert read({"traces": [{"path": "/nonexistent/x.xplane.pb"}]}) is None
    assert read({"traces": []}) is None and read({}) is None


def test_manifest_has_the_entry_and_its_reader():
    m = mf.Manifest(REPO)
    assert mf.check(m) == []
    entry = m.per_layer[METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "engine scheduler",
        "moves": "out_tok_per_s", "workloads": ["chat-steady",
                                                "decode-heavy"]}
    # the layer's name as the benchmark already has it, letter for letter
    assert m.per_layer["decode_window_steps_mean"]["layer"] == entry["layer"]
    for cell in entry["workloads"]:
        assert METRIC in {x["name"] for x in m.metrics_for(cell, "per_layer")}
        assert "out_tok_per_s" in {
            x["name"] for x in m.metrics_for(cell, "end_to_end")}
    # the cells whose per-layer sets other tests of the benchmark hold
    for cell in ("hybrid-decode-heavy", "sdar-decode-heavy",
                 "jamba-prompt-heavy", "train-2k"):
        assert METRIC not in {x["name"]
                              for x in m.metrics_for(cell, "per_layer")}
    # after the entries the benchmark had
    names = [x["name"] for x in m.data["per_layer"]]
    assert names.index(METRIC) > names.index("decode_window_steps_mean")


@pytest.mark.parametrize("pipeline,want", [(True, 100.0 * 18 / 19),
                                           (False, 0.0)])
def test_spans_of_a_real_engine_say_which_windows_were_chained(
        monkeypatch, tmp_path, read, pipeline, want):
    """Three requests through two slots of a tiny Llama on the CPU: one
    ends inside a window and the third takes its slot while the others'
    window is in flight. Pipelined, one busy stretch has one unchained
    dispatch, its first; unpipelined, none is chained. The reader gives the
    share of the engine's own spans."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.tiny())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, EngineConfig(
        max_seqs=2, page_size=8, max_pages_per_seq=16,
        prefill_buckets=(32,), decode_steps=4, pipeline_dispatch=pipeline))
    fr._ring.clear()    # a bounded ring: a position in it does not last
    for rid, n in (("a", 50), ("b", 7), ("c", 9)):
        eng.add_request(Request(rid, list(range(1, 12)), max_tokens=n))
    emitted = 0
    while eng.has_work():
        emitted += len(eng.step())
    assert emitted == 66
    spans = [e["args"] for e in fr.dump_events()
             if e.get("kind") == "span" and e["name"] == SPAN]
    assert {s["across"] for s in spans} == (
        {"none", "finish", "admission"} if pipeline else {"none"})
    chained = [s["chained"] for s in spans]
    assert chained == ([False] + [True] * (len(spans) - 1) if pipeline
                       else [False] * len(spans))
    got = read(_obs(monkeypatch, tmp_path, chained))
    assert got == pytest.approx(100.0 * sum(chained) / len(chained))
    assert got == pytest.approx(want)
