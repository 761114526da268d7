"""`decode_window_steps_mean`: the mean `steps` of the engine's
`ray_tpu.engine.dispatch_decode` spans (8 where no window was shortened,
4 where every one was), on hand-made spans, in the manifest, and against
what a real engine on the CPU emits in the windows it reports."""

from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO
from benchmark import manifest as mf
from benchmark import program_trace

METRIC = "decode_window_steps_mean"
SPAN = "ray_tpu.engine.dispatch_decode"


def _obs(monkeypatch, tmp_path, steps, name=SPAN):
    """An observation whose one trace file holds a `dispatch_decode` span
    for each of `steps`."""
    events = [NS(name=name, start_ns=1e5 * i, duration_ns=5e3,
                 stats=[("active", 3), ("max_seqs", 16), ("steps", k),
                        ("free_slots", 13), ("chained", 1)])
              for i, k in enumerate(steps)]
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


@pytest.mark.parametrize("steps,want", [
    ([8, 4, 4] * 2, 16 / 3),            # 5.33: a third of the windows whole
    ([8] * 7, 8.0),                     # the mechanism never engaged
    ([4] * 40, 4.0),                    # always
    ([4, 4, 8, 8, 2], 5.2),
    ([8, 4, 4], None),                  # under MIN_EVENTS: nothing to average
    ([4] * (program_trace.MIN_EVENTS - 1), None),
    ([], None),
])
def test_reader_on_hand_made_spans(monkeypatch, tmp_path, read, steps, want):
    obs = _obs(monkeypatch, tmp_path, steps)
    got = read(obs)
    assert got == (None if want is None else pytest.approx(want))


def test_reader_finds_nothing_without_the_span_or_a_trace(
        monkeypatch, tmp_path, read):
    # a program that opens another span only; a span without `steps`
    assert read(_obs(monkeypatch, tmp_path, [4] * 9,
                     name="ray_tpu.engine.step")) is None
    assert read({"traces": [{"path": "/nonexistent/x.xplane.pb"}]}) is None
    assert read({"traces": []}) is None and read({}) is None


def test_manifest_has_the_entry_and_its_reader():
    m = mf.Manifest(REPO)
    assert mf.check(m) == []
    entry = m.per_layer[METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower",
        "source": "program_span", "layer": "engine scheduler",
        "moves": "tpot_p95_ms", "workloads": ["chat-steady", "decode-heavy"]}
    # the layer's name as the benchmark already has it, letter for letter
    assert m.per_layer["queue_wait_mean_ms"]["layer"] == entry["layer"]
    for cell in entry["workloads"]:
        assert METRIC in {x["name"] for x in m.metrics_for(cell, "per_layer")}
        assert "tpot_p95_ms" in {
            x["name"] for x in m.metrics_for(cell, "end_to_end")}
    # the cells whose per-layer sets other tests of the benchmark hold
    for cell in ("hybrid-decode-heavy", "sdar-decode-heavy",
                 "jamba-prompt-heavy", "train-2k"):
        assert METRIC not in {x["name"]
                              for x in m.metrics_for(cell, "per_layer")}
    # after the entries the benchmark had
    names = [x["name"] for x in m.data["per_layer"]]
    assert names.index(METRIC) > names.index("ssm_kernels_pct")


@pytest.mark.parametrize("max_seqs", [1, 2])
def test_span_of_a_real_engine_says_the_tokens_a_row_emitted(max_seqs):
    """One request through a tiny Llama on the CPU, with a slot free
    (`max_seqs` 2: half windows) and without (1: whole ones): every
    `dispatch_decode` span carries `free_slots`, and `steps` equal to the
    tokens the row emitted in that window (its last window: what was left
    of the request)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.tiny())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, EngineConfig(
        max_seqs=max_seqs, page_size=8, max_pages_per_seq=16,
        prefill_buckets=(32,), decode_steps=8))
    fr._ring.clear()    # a bounded ring: a position in it does not last
    eng.add_request(Request("a", list(range(1, 12)), max_tokens=31))
    emitted = 0
    while eng.has_work():
        emitted += len(eng.step())
    assert emitted == 31
    events = [e for e in fr.dump_events() if e.get("kind") == "span"]
    spans = [e["args"] for e in events if e["name"] == SPAN]
    emits = [e["args"]["tokens"] for e in events
             if e["name"] == "ray_tpu.engine.emit"]
    want = 8 if max_seqs == 1 else 4
    assert len(spans) == len(emits) == -(-30 // want)
    assert all(s["free_slots"] == max_seqs - 1 and s["steps"] == want
               and s["max_seqs"] == max_seqs for s in spans)
    # the prefill made the first token; the windows' rows made the rest
    assert emits == [want] * (30 // want) + [30 % want] * bool(30 % want)
    assert sum(s["steps"] for s in spans) / len(spans) == want
