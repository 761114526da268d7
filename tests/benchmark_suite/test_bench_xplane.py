"""The reduction from a profiler trace to busy time, modules, operations
and labelled idle gaps: its arithmetic on a hand-made trace, and its reading
of a small trace recorded on the chip (benchmark/fixtures/)."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO
from benchmark import xplane

FIXTURE = os.path.join(REPO, "benchmark", "fixtures",
                       "decode_slice.xplane.pb.gz")
EXPECTED = os.path.join(REPO, "benchmark", "fixtures",
                        "decode_slice.expected.json")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=[])


def _trace():
    """Window 0..1000 us. Device: a `while` (100..400) holding two ops of
    the decode program, idle 400..700 while the host is in
    `bench.engine.admit`, an op 700..900, idle to the end with no span."""
    us = 1000
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_decode(123)", 100 * us, 300 * us),
            _ev("jit_prefill(77)", 700 * us, 200 * us),
            _ev("jit_decode(123)", 950 * us, 500 * us)]),   # crosses the end
        NS(name="XLA Ops", events=[
            _ev("%while.9 = (s32[]{:T(128)}, bf16[8,64]{1,0}) while(%t), "
                "body=%b", 100 * us, 300 * us),
            _ev("%fusion.1 = bf16[16,14336]{1,0:T(8,128)(2,1)} fusion(bf16"
                "[16,4096]{1,0} %x), kind=kOutput", 100 * us, 200 * us),
            _ev("%self_attn.2 = bf16[16,8,4,128]{3,2,1,0} custom-call(%q)",
                300 * us, 80 * us),
            _ev("%fusion.7 = bf16[16,14336]{1,0:T(8,128)(2,1)} fusion(bf16"
                "[16,4096]{1,0} %y), kind=kOutput", 700 * us, 200 * us)]),
        NS(name="Steps", events=[_ev("0", 0, 1000 * us)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 1000 * us),
        _ev("bench.engine.step", 50 * us, 700 * us),
        _ev("bench.engine.admit", 380 * us, 330 * us),
        _ev("PjitFunction(decode)", 90 * us, 20 * us)])])
    return NS(planes=[host, device, NS(name="Task Environment", lines=[])])


def test_reduce_busy_modules_ops_and_gaps():
    r = xplane.reduce(_trace())
    assert r["devices"] == 1 and r["has_window_span"]
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx((300 + 200) * 1e-6)   # union
    assert r["modules"] == {
        "jit_decode": {"count": 1, "seconds": pytest.approx(300e-6)},
        "jit_prefill": {"count": 1, "seconds": pytest.approx(200e-6)}}
    # self time, by module, opcode and result; the same op of two layers
    # (or two launches) falls under one label
    assert dict(map(tuple, r["device_ops"])) == {
        "jit_prefill: fusion bf16[16,14336] x1": pytest.approx(200e-6),
        "jit_decode: fusion bf16[16,14336] x1": pytest.approx(200e-6),
        "jit_decode: custom-call bf16[16,8,4,128] (self_attn) x1":
            pytest.approx(80e-6),
        "jit_decode: while (s32[], bf16[8,64]) x1": pytest.approx(20e-6)}
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.engine.admit"] == pytest.approx(300e-6)
    # a gap takes the label of the span open at its midpoint: 0..100 -> 50
    assert gaps["bench.engine.step"] == pytest.approx(100e-6)
    assert gaps["no bench span open"] == pytest.approx(100e-6)   # 900..1000
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_without_a_window_span_or_a_device():
    t = _trace()
    t.planes[0].lines[0].events = []
    r = xplane.reduce(t)
    assert not r["has_window_span"]
    assert r["window_s"] == pytest.approx(800e-6)     # first to last op
    assert xplane.reduce(NS(planes=[t.planes[0]])) == {}
    assert xplane.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert xplane.module_name("jit_step(9912)") == "jit_step"


def test_reduce_a_trace_recorded_on_the_chip(tmp_path):
    """A slice of chat-steady's window on a v5e (PR 23): the reduction must
    keep reading it as it did when it was recorded."""
    path = tmp_path / "t.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    r = xplane.reduce(xplane.load(str(path)))
    with open(EXPECTED) as f:
        want = json.load(f)
    assert r["devices"] == 1 and r["has_window_span"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert {k: v["count"] for k, v in r["modules"].items()} == \
        want["module_counts"]
    assert any("decode" in name for name in r["modules"])
    assert [name for name, _ in r["device_ops"][:3]] == want["top_ops"]
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"]
    labels = {name for name, _ in r["idle_gaps"]}
    assert labels & {"bench.engine.step", "bench.engine.admit",
                     "bench.engine.wait_tokens", "no bench span open"}
