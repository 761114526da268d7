"""The program's own spans, counters, kernel names and compile record.

A tiny Llama behind a real `LLMServer` under `jax.profiler`: the spans the
engine and the server open (`flight_recorder.span`/`mark`) arrive in the
profiler's trace with their arguments, nested as the code nests, and in the
recorder's ring. One case per span or name, so that each counts."""

import dataclasses
import glob
import subprocess
import sys
import threading
import time
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import flight_recorder as fr

ENGINE_SPANS = {
    "ray_tpu.engine.step": {"running", "waiting", "inflight"},
    "ray_tpu.engine.admit": {"admitted", "waiting_left", "free_slots",
                             "free_pages"},
    "ray_tpu.engine.prefill_dispatch": {"bucket", "nb", "tokens",
                                        "cached_tokens", "rich", "want_lp",
                                        "new_program", "state_rows",
                                        "scan_positions", "head_rows"},
    "ray_tpu.engine.prefill_sync": {"requests"},
    "ray_tpu.engine.dispatch_decode": {"active", "max_seqs", "steps",
                                       "free_slots", "chained", "across",
                                       "new_program",
                                       "state_rows", "block_length",
                                       "denoise_passes", "commit_passes",
                                       "fused_commits", "fresh_rows"},
    "ray_tpu.engine.wait_tokens": {"why"},
    "ray_tpu.engine.emit": {"tokens", "finished", "skipped"},
}
SERVER_SPANS = {
    "ray_tpu.server.deliver": {"outputs"},
    "ray_tpu.server.idle": set(),
}
MARKS = {
    "ray_tpu.request.first_token": {"rid", "slot", "queue_ms", "prefill_ms",
                                    "prompt", "cached", "nb"},
    "ray_tpu.request.finished": {"rid", "slot", "decode_ms", "tokens"},
}
WHYS = {"admitted", "idle", "all_finishing", "chained",
        "unpipelined"}


def _server(**engine_config):
    from ray_tpu.llm._internal.server import LLMServer

    cfg = {"max_seqs": 2, "page_size": 8, "max_pages_per_seq": 16,
           "decode_steps": 2, "prefill_buckets": (32,)}
    cfg.update(engine_config)
    return LLMServer({"model": "tiny", "engine_config": cfg})


def _profiler_events(log_dir):
    """The `ray_tpu.*` events of the newest trace under `log_dir`."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ray_tpu."):
                    out.append({"name": e.name, "line": line.name,
                                "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "stats": dict(e.stats)})
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three requests through two slots under the profiler: A runs alone,
    the second is admitted while A's decode window is in flight (the next
    window is queued behind its prefill, inside the admission), the third,
    made once the engine holds the second, has to wait for a slot; after an
    idle stretch a fourth ends it."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    srv = _server()
    srv.generate_all([5, 6, 7], max_tokens=3)       # build the programs
    fr._ring.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        results = {}
        stream = srv.generate(list(range(1, 12)), max_tokens=110)
        first = [next(stream) for _ in range(3)]    # A is decoding

        def run(name, prompt, n):
            results[name] = srv.generate_all(prompt, max_tokens=n)

        others = [threading.Thread(target=run, args=("B", [9] * 20, 90)),
                  threading.Thread(target=run, args=("C", [3] * 9, 6))]
        others[0].start()
        # C is made only once the engine holds B (queued or admitted): the
        # two threads' start order decides nothing.
        eng, deadline = srv.engine, time.monotonic() + 60
        while len(eng.waiting) + len(eng.running) < 2:
            assert time.monotonic() < deadline, "B never reached the engine"
            time.sleep(0.001)
        others[1].start()
        results["A"] = first + list(stream)
        for t in others:
            t.join(120)
        time.sleep(0.05)                             # an idle stretch,
        results["D"] = srv.generate_all([1, 2], max_tokens=2)  # and its end
    finally:
        jax.profiler.stop_trace()
    srv._running = False
    assert len(results["A"]) == 110
    assert len(results["B"]["tokens"]) == 90
    assert len(results["C"]["tokens"]) == 6
    return {"events": _profiler_events(log_dir), "ring": fr.dump_events(),
            "server": srv}


def _named(traced, name):
    return [e for e in traced["events"] if e["name"] == name]


@pytest.mark.parametrize("name", sorted(ENGINE_SPANS))
def test_engine_span_in_the_profile_with_its_arguments(traced, name):
    spans = _named(traced, name)
    assert spans, f"no {name} in the profiler's trace"
    for e in spans:
        assert set(e["stats"]) == ENGINE_SPANS[name], e
    steps = _named(traced, "ray_tpu.engine.step")
    if name != "ray_tpu.engine.step":
        for e in spans:     # nested: same thread line, inside one step
            assert any(s["line"] == e["line"] and s["start"] <= e["start"]
                       and e["end"] <= s["end"] for s in steps), e


@pytest.mark.parametrize("name", sorted(SERVER_SPANS))
def test_server_span_in_the_profile(traced, name):
    spans = _named(traced, name)
    assert spans, f"no {name} in the profiler's trace"
    for e in spans:
        assert set(e["stats"]) == SERVER_SPANS[name]
    steps = _named(traced, "ray_tpu.engine.step")
    # beside the engine's steps on the engine thread, not inside them
    assert {e["line"] for e in spans} == {steps[0]["line"]}
    for e in spans:
        assert not any(s["start"] <= e["start"] < s["end"] for s in steps)


@pytest.mark.parametrize("name", sorted(MARKS))
def test_request_mark_in_the_profile(traced, name):
    marks = _named(traced, name)
    assert len(marks) == 4
    for e in marks:
        assert set(e["stats"]) == MARKS[name]


def test_first_token_and_finished_share_a_rid(traced):
    first = {e["stats"]["rid"]: e for e in
             _named(traced, "ray_tpu.request.first_token")}
    last = {e["stats"]["rid"]: e for e in
            _named(traced, "ray_tpu.request.finished")}
    assert len(first) == 4 and set(first) == set(last)
    for rid, e in first.items():
        assert e["start"] < last[rid]["start"]
        assert e["stats"]["slot"] == last[rid]["stats"]["slot"]
    assert sorted(e["stats"]["tokens"] for e in last.values()) == [2, 6, 90, 110]
    assert sorted(e["stats"]["prompt"] for e in first.values()) == [2, 9, 11, 20]


def test_the_third_request_waits_for_a_slot(traced):
    marks = sorted(_named(traced, "ray_tpu.request.first_token"),
                   key=lambda e: e["start"])
    freed = min(e["start"] for e in
                _named(traced, "ray_tpu.request.finished"))
    assert marks[1]["start"] < freed < marks[2]["start"]
    assert all(e["stats"]["prefill_ms"] > 0 and e["stats"]["queue_ms"] >= 0
               for e in marks)
    # the waiting request shows in the steps' counters meanwhile
    assert any(e["stats"]["waiting"] == 1 and e["stats"]["running"] == 2
               for e in _named(traced, "ray_tpu.engine.step"))


def test_queue_ms_grows_when_a_request_waits_for_a_slot():
    """Straight through the engine: two requests take the two slots, a
    third is made 60 ms before the engine is stepped at all."""
    from ray_tpu.llm._internal.engine import Request

    eng = _engine()
    late = Request("late", [7, 8, 9], max_tokens=2)
    time.sleep(0.06)
    for rid in ("a", "b"):
        eng.add_request(Request(rid, [1, 2, 3, 4], max_tokens=4))
    eng.add_request(late)
    fr._ring.clear()
    while eng.has_work():
        eng.step()
    first = {e["args"]["rid"]: e["args"] for e in fr.dump_events()
             if e.get("name") == "ray_tpu.request.first_token"}
    assert set(first) == {"a", "b", "late"}
    assert first["late"]["queue_ms"] >= 60.0
    assert first["late"]["queue_ms"] > max(first["a"]["queue_ms"],
                                           first["b"]["queue_ms"]) + 50.0
    assert first["a"]["nb"] == first["b"]["nb"] == 2    # one admission
    assert first["late"]["nb"] == 1


def test_wait_tokens_says_why(traced):
    waits = _named(traced, "ray_tpu.engine.wait_tokens")
    whys = [e["stats"]["why"] for e in waits]
    assert set(whys) <= WHYS
    assert "chained" in whys            # the pipelined steady state
    # No admission and no finish drained a window: a Llama's chain of
    # windows outlives both.
    assert "admitted" not in whys and "finished_in_chain" not in whys
    # B's admission found A's window in flight and queued the next one
    # behind B's prefill, inside its `admit` span and before the first token
    # was read; the step then waited for A's window with that one behind it
    admits = [e for e in _named(traced, "ray_tpu.engine.admit")
              if e["stats"]["admitted"] >= 1]
    inside = lambda e, outer: (outer["start"] <= e["start"]
                               and e["end"] <= outer["end"])
    decode = _named(traced, "ray_tpu.engine.dispatch_decode")
    across = [d for d in decode if d["stats"]["across"] == "admission"]
    assert across and all(d["stats"]["chained"] for d in across)
    steps = _named(traced, "ray_tpu.engine.step")
    syncs = _named(traced, "ray_tpu.engine.prefill_sync")
    for d in across:
        admit = next(a for a in admits if inside(d, a))
        sync = next(s for s in syncs if inside(s, admit))
        assert d["end"] <= sync["start"]
        step = next(s for s in steps if inside(admit, s))
        assert step["stats"]["inflight"] == 1
        (wait,) = [w for w in waits if inside(w, step)]
        assert wait["stats"]["why"] == "chained"
        assert admit["end"] <= wait["start"]
    # every other dispatch lies outside every admission
    assert not any(inside(d, a) for d in decode if d not in across
                   for a in admits)
    # C took the slot B or A left while a window with that row was in
    # flight: the chain outlived the finish too
    assert "finish" in {d["stats"]["across"] for d in decode}


def test_decode_rows_and_admission_counters_are_exact(traced):
    for e in _named(traced, "ray_tpu.engine.dispatch_decode"):
        assert e["stats"]["max_seqs"] == 2
        assert 1 <= e["stats"]["active"] <= 2
        # half a window of two while one of the two slots is free
        assert e["stats"]["free_slots"] == 2 - e["stats"]["active"]
        assert e["stats"]["steps"] == (1 if e["stats"]["free_slots"] else 2)
    assert {e["stats"]["active"] for e in
            _named(traced, "ray_tpu.engine.dispatch_decode")} == {1, 2}
    pre = _named(traced, "ray_tpu.engine.prefill_dispatch")
    assert sorted(e["stats"]["tokens"] for e in pre) == [2, 9, 11, 20]
    assert all(e["stats"]["nb"] == 1 and e["stats"]["bucket"] == 32
               and not e["stats"]["new_program"] for e in pre)
    emitted = sum(e["stats"]["tokens"] for e in
                  _named(traced, "ray_tpu.engine.emit"))
    assert emitted == 110 + 90 + 6 + 2 - 4   # first tokens come from prefill
    assert sum(e["stats"]["finished"] for e in
               _named(traced, "ray_tpu.engine.emit")) == 4


def test_ring_and_chrome_trace_hold_the_spans(traced):
    ring = [e for e in traced["ring"] if e["kind"] == "span"]
    names = {e["name"] for e in ring}
    assert set(ENGINE_SPANS) | set(SERVER_SPANS) | set(MARKS) <= names
    for e in ring:
        assert (e["dur_us"] is None) == (e["name"] in MARKS
                                         or ".program." in e["name"])
    rows = fr.chrome_trace_events(traced["ring"], pid="p")
    admit = [r for r in rows if r["name"] == "ray_tpu.engine.admit"]
    assert admit and all(r["ph"] == "X" and r["dur"] > 0
                         and r["tid"] == "llm-engine"
                         and "admitted" in r["args"] for r in admit)
    marks = [r for r in rows if r["name"] == "ray_tpu.request.finished"]
    assert len(marks) == 4 and all(r["ph"] == "i" for r in marks)
    # a step's row holds its admission's row on the same chrome thread
    steps = [r for r in rows if r["name"] == "ray_tpu.engine.step"]
    a = admit[0]
    assert any(s["tid"] == a["tid"] and s["ts"] <= a["ts"]
               and a["ts"] + a["dur"] <= s["ts"] + s["dur"] + 1.0
               for s in steps)


def test_latency_histograms_of_the_metrics_plane(traced):
    from ray_tpu.util import metrics as um

    for name in ("ray_tpu_llm_queue_wait_seconds",
                 "ray_tpu_llm_prefill_seconds"):
        values = um._named[name].snapshot()["values"]
        assert sum(v["count"] for v in values.values()) >= 4


def test_programs_counts_a_built_program_once_and_records_a_retrace():
    srv = _server()
    eng = srv.engine
    try:
        assert srv.stats()["programs"] == {"built": 0, "retraced": 0,
                                           "records": []}
        srv.generate_all([1, 2, 3], max_tokens=5)
        srv.generate_all([4, 5, 6, 7], max_tokens=5)   # the same programs
        got = srv.stats()["programs"]
        assert got["built"] == 2 and got["retraced"] == 0
        assert [(r["event"], r["kind"], r["key"]) for r in got["records"]] \
            == [("built", "prefill", [32, 1, False, False]),
                ("built", "decode", [False, False])]
        assert all(r["seconds"] > 0 for r in got["records"])
        # The same key, an argument of another signature: the PRNG keys
        # committed to a device. The table holds the program; its jit
        # cache grows.
        while eng.has_work() or eng._inflight is not None:
            time.sleep(0.01)
        eng._keys_dev = jax.device_put(eng._keys_dev, jax.devices()[0])
        srv.generate_all([1, 2, 3], max_tokens=1)
        got = srv.stats()["programs"]
        assert got["built"] == 2 and got["retraced"] == 1
        last = got["records"][-1]
        assert (last["event"], last["kind"]) == ("retraced", "prefill")
        (path, (before, after)), = last["differs"].items()
        assert path == "[9]" and before[:3] == after[:3]
        assert (before[4], after[4]) == (False, True)   # committed
        from ray_tpu.util import metrics as um

        counted = um._named["ray_tpu_llm_programs_built_total"].snapshot()
        assert sum(counted["values"].values()) >= 3
    finally:
        srv._running = False


def test_request_is_stamped_when_it_is_made():
    from ray_tpu.llm._internal.engine import Request

    t0 = time.monotonic()
    req = Request("r", [1, 2], 4, 0.0)      # positional fields as before
    assert t0 <= req.t_enqueued <= time.monotonic()
    assert (req.max_tokens, req.temperature, req.slot) == (4, 0.0, -1)
    names = [f.name for f in dataclasses.fields(Request)]
    assert names[:4] == ["request_id", "prompt_ids", "max_tokens",
                         "temperature"]


def test_primitive_imports_no_jax_in_a_process_without_it():
    code = (
        "import sys\n"
        "from ray_tpu._private import flight_recorder as fr\n"
        "with fr.span('ray_tpu.x', a=1) as sp:\n"
        "    sp.set(b=2)\n"
        "    fr.mark('ray_tpu.m', rid='r')\n"
        "ev = fr.dump_events()\n"
        "assert [e['name'] for e in ev] == ['ray_tpu.m', 'ray_tpu.x'], ev\n"
        "assert ev[1]['args'] == {'a': 1, 'b': 2} and ev[1]['dur_us'] > 0\n"
        "assert 'jax' not in sys.modules, 'the primitive imported jax'\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_disabled_recorder_hands_out_one_shared_no_op():
    fr.set_enabled(False)
    try:
        before = len(fr.dump_events())
        a, b = fr.span("ray_tpu.x", n=1), fr.span("ray_tpu.y")
        assert a is b
        with a as sp:
            sp.set(k=1)
        fr.mark("ray_tpu.m")
        assert len(fr.dump_events()) == before
    finally:
        fr.set_enabled(True)


def _tiny(**over):
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(dataclasses.replace(LlamaConfig.tiny(), **over))
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def _engine():
    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine

    model, shapes = _tiny()
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return LLMEngine(model, params, EngineConfig(
        max_seqs=2, page_size=8, max_pages_per_seq=8))


def _train_step():
    import optax

    from ray_tpu.train.step import init_train_state, make_train_step

    model, _ = _tiny(attention_impl="flash")
    opt = optax.sgd(0.1)
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    state = jax.eval_shape(lambda: init_train_state(
        model, opt, jnp.zeros(ids.shape, ids.dtype)))
    return make_train_step(model, opt), (state, ids, ids)


@pytest.mark.parametrize("which,name", [
    ("decode", "decode"), ("prefill", "prefill"), ("train", "step")])
def test_jitted_functions_keep_the_names_the_trace_readers_match(which,
                                                                 name):
    """The trace's `XLA Modules` are `jit_<name>`; the benchmark's readers
    match on them."""
    if which == "train":
        fn = _train_step()[0]
        assert hasattr(fn, "lower")     # the jitted function itself
    else:
        eng = _engine()
        fn = (eng._decode_fn(False, False) if which == "decode"
              else eng._prefill_fn(32))
    assert fn.__name__ == name


def _lowered_for_tpu(fn, args) -> str:
    """StableHLO as the program lowers for a TPU, from shapes alone. The
    kernels' dispatch asks the default backend; here it is told."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


@pytest.fixture(scope="module")
def lowered():
    eng = _engine()
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        eng._decode_args())
    step, args = _train_step()
    return {"decode": _lowered_for_tpu(eng._decode_fn(False, False), shapes),
            "train": _lowered_for_tpu(step, args)}


@pytest.mark.parametrize("program,kernel", [
    ("decode", "paged_decode"), ("train", "flash_fwd"),
    ("train", "flash_bwd_dq"), ("train", "flash_bwd_dkv")])
def test_lowered_programs_name_their_kernels(lowered, program, kernel):
    text = lowered[program]
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{kernel}"' in text
