"""The program's own spans and kernel names, read back from a trace:
`benchmark/program_trace.py` and the eight readers on top of it, on a
hand-made trace and on a small trace recorded on the chip
(benchmark/fixtures/program_spans.*)."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO
from benchmark import manifest as mf
from benchmark import program_trace

FIXTURE = os.path.join(REPO, "benchmark", "fixtures",
                       "program_spans.xplane.pb.gz")
EXPECTED = os.path.join(REPO, "benchmark", "fixtures",
                        "program_spans.expected.json")
NEW_METRICS = {
    "queue_wait_mean_ms": ("ms", "engine scheduler", "ttft_p95_ms",
                           ["chat-steady"]),
    "prefill_mean_ms": ("ms", "jitted prefill and decode", "ttft_p95_ms",
                        ["chat-steady"]),
    "admit_batch_mean": ("count", "engine scheduler", "ttft_p95_ms",
                         ["chat-steady"]),
    # not `decode-heavy`: its 4 s slice holds three admissions, under the
    # readers' floor of five events
    "admit_stall_mean_ms": ("ms", "engine scheduler", "tpot_p95_ms",
                            ["chat-steady"]),
    "decode_rows_active_pct": ("%", "engine scheduler", "out_tok_per_s",
                               ["chat-steady", "decode-heavy"]),
    "paged_decode_kernel_us": ("us", "kernels", "tpot_p95_ms",
                               ["chat-steady", "decode-heavy"]),
    "flash_fwd_kernel_ms": ("ms", "kernels", "train_tok_per_s",
                            ["train-2k"]),
    "flash_bwd_kernel_ms": ("ms", "kernels", "train_tok_per_s",
                            ["train-2k"]),
}


def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _trace(n=6):
    """`n` admissions of `i + 1` requests that each stall 100 + i us, `n`
    decode dispatches with i + 1 of 8 rows in use, and per kernel `n`
    calls; a fusion that borrows a kernel's name and a host event that is
    not the program's must not count."""
    host, ops = [_ev("bench.window", 0, 1000)], []
    for i in range(n):
        t = 100 * i
        host += [
            _ev("ray_tpu.engine.step", t, 90, running=i, waiting=1,
                inflight=1),
            _ev("ray_tpu.engine.admit", t + 1, 100 + i, admitted=i + 1,
                waiting_left=0, free_slots=8 - i, free_pages=64),
            _ev("ray_tpu.engine.prefill_dispatch", t + 2, 10, bucket=128,
                nb=i + 1, tokens=90, cached_tokens=0, rich=0, want_lp=0,
                new_program=0),
            _ev("ray_tpu.request.first_token", t + 20, 0, rid=f"r{i}",
                slot=i, queue_ms=10.0 * i, prefill_ms=30.0 + i, prompt=90,
                cached=0, nb=i + 1),
            _ev("ray_tpu.engine.dispatch_decode", t + 30, 5, active=i + 1,
                max_seqs=8, steps=8, chained=1, new_program=0),
            _ev("bench.engine.admit", t + 1, 100),
            _ev("PjitFunction(decode)", t + 30, 4)]
        ops += [
            _ev(f"%paged_decode.{i} = bf16[8,8,4,128]{{3,2,1,0}} "
                "custom-call(%pt, %q)", t + 40, 10 + i),
            _ev(f"%flash_fwd.{i} = (bf16[8,32,2048,128]{{3,2,1,0}}, f32[8,"
                "32,2048,1]{3,2,1,0}) custom-call(%q)", t + 50, 1000),
            _ev(f"%flash_bwd_dq.{i} = bf16[8,32,2048,128]{{3,2,1,0}} "
                "custom-call(%q)", t + 60, 2000),
            _ev(f"%flash_bwd_dkv.{i} = (bf16[8,32,2048,128]{{3,2,1,0}}, "
                "bf16[8,32,2048,128]{3,2,1,0}) custom-call(%q)", t + 70,
                3000),
            _ev(f"%paged_decode_fusion.{i} = bf16[16]{{0}} fusion(%x)",
                t + 80, 500),
            _ev(f"%self_attn.{i} = bf16[8,8,4,128]{{3,2,1,0}} "
                "custom-call(%q)", t + 90, 7)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="llm-engine", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[_ev("jit_decode(1)", 0, 900)]),
            NS(name="XLA Ops", events=ops)]),
        NS(name="Task Environment", lines=[])])


@pytest.fixture
def obs(monkeypatch, tmp_path):
    """An observation whose one trace file reads as `_trace()`."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    traces = {str(path): _trace()}
    monkeypatch.setattr(program_trace.xplane, "load", traces.__getitem__)
    program_trace._read.cache_clear()
    yield {"traces": [{"path": str(path), "window_s": 0.001}],
           "rewrite": lambda n: (traces.__setitem__(str(path), _trace(n)),
                                 program_trace._read.cache_clear())}
    program_trace._read.cache_clear()


def test_reduce_events_stats_and_kernel_time():
    r = program_trace.reduce(_trace())
    assert r["window_s"] == pytest.approx(1e-3)
    names = {e["name"] for e in r["events"]}
    assert names == {"ray_tpu.engine.step", "ray_tpu.engine.admit",
                     "ray_tpu.engine.prefill_dispatch",
                     "ray_tpu.request.first_token",
                     "ray_tpu.engine.dispatch_decode"}
    starts = [e["start_ns"] for e in r["events"]]
    assert starts == sorted(starts)
    first = next(e for e in r["events"]
                 if e["name"] == "ray_tpu.request.first_token")
    assert first["stats"]["rid"] == "r0" and first["stats"]["nb"] == 1
    # custom-calls by the instruction's own name; the fusion and the
    # unnamed custom-call are left out
    assert r["kernels"] == {
        "paged_decode": {"count": 6,
                         "seconds": pytest.approx(sum(range(10, 16)) * 1e-6)},
        "flash_fwd": {"count": 6, "seconds": pytest.approx(6e-3)},
        "flash_bwd_dq": {"count": 6, "seconds": pytest.approx(12e-3)},
        "flash_bwd_dkv": {"count": 6, "seconds": pytest.approx(18e-3)}}
    assert program_trace.instruction_name(
        "%flash_fwd.12 = bf16[1]{0} custom-call()") == "flash_fwd.12"
    assert program_trace.instruction_name("no instruction") == ""


@pytest.mark.parametrize("metric,want", [
    ("queue_wait_mean_ms", 25.0),               # mean of 0, 10, .. 50
    ("prefill_mean_ms", 32.5),                  # mean of 30 .. 35
    ("admit_batch_mean", 3.5),                  # mean of 1 .. 6
    ("admit_stall_mean_ms", 0.1025),            # mean of 100 .. 105 us
    ("decode_rows_active_pct", 100 * 21 / 48),  # 1 + .. + 6 of 6 x 8
    ("paged_decode_kernel_us", 12.5),           # mean of 10 .. 15
    ("flash_fwd_kernel_ms", 1.0),
    ("flash_bwd_kernel_ms", 5.0),               # dq 2 + dkv 3 per layer
])
def test_reader_on_a_hand_made_trace(obs, metric, want):
    read = mf.Manifest(REPO).reader(metric)
    assert read(obs) == pytest.approx(want)
    # fewer than five of its events: nothing to average
    obs["rewrite"](4)
    assert read(obs) is None
    # a program without the spans (the parent), a run without a trace
    assert read({"traces": [{"path": "/nonexistent/x.xplane.pb"}]}) is None
    assert read({"traces": []}) is None and read({}) is None


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_manifest_has_the_entry_and_its_reader(metric):
    m = mf.Manifest(REPO)
    unit, layer, moves, cells = NEW_METRICS[metric]
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["layer"], entry["moves"],
            entry["workloads"]) == (unit, layer, moves, cells)
    assert entry["source"] in ("program_span", "program_counter",
                               "device_trace")
    assert callable(m.reader(metric))
    # a layer the benchmark already names, letter for letter
    assert layer in {x["layer"] for x in m.data["per_layer"][:12]}
    for cell in cells:
        assert metric in {x["name"]
                          for x in m.metrics_for(cell, "per_layer")}


def test_program_spans_fixture_recorded_on_the_chip(tmp_path):
    """One second of the program on a v5e with its spans and its named
    kernels (PR 24): the reduction and every reader must keep reading it
    as they did when it was recorded."""
    path = tmp_path / "t.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    assert os.path.getsize(FIXTURE) < 1_500_000
    with open(EXPECTED) as f:
        want = json.load(f)
    r = program_trace.read(str(path))
    assert 0 < r["window_s"] <= 1.0
    counts = {}
    for e in r["events"]:
        counts[e["name"]] = counts.get(e["name"], 0) + 1
    assert counts == want["event_counts"]
    assert {k: v["count"] for k, v in r["kernels"].items()} == \
        want["kernel_counts"]
    assert set(r["kernels"]) == set(program_trace.KERNELS)
    obs = {"traces": [{"path": str(path)}]}
    m = mf.Manifest(REPO)
    for metric, value in want["metrics"].items():
        assert m.reader(metric)(obs) == pytest.approx(value, rel=1e-9)
    assert set(want["metrics"]) == set(NEW_METRICS)
    # the spans carry their counters on the chip as they do on the CPU
    admit = next(e for e in r["events"]
                 if e["name"] == "ray_tpu.engine.admit")
    assert {"admitted", "waiting_left", "free_slots",
            "free_pages"} <= set(admit["stats"])
    whys = {e["stats"]["why"] for e in r["events"]
            if e["name"] == "ray_tpu.engine.wait_tokens"}
    assert whys and whys <= {"admitted", "idle", "all_finishing", "chained",
                             "finished_in_chain", "unpipelined"}
