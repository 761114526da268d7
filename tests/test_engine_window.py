"""The length of a decode window follows from whether a request could be
admitted at its end (`LLMEngine._window_steps`), and is an argument of the
one decode program, not a program of its own: however a run is cut into
windows it makes the same tokens, logprobs and cache. Tiny models of the
three families that yield a token a forward, float32 on the CPU."""

import functools
import itertools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from engine_sharing import prompt_ids as _ids  # noqa: E402
from engine_sharing import tiny_family  # noqa: E402
from ray_tpu._private import flight_recorder as fr  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402

FULL = 8
# 24 decode steps (a request of 25 tokens: prefill makes the first) cut
# three ways, none of which runs a step past the request's end, so the
# caches can be compared whole.
CUTS = {"whole": [8], "halves": [4], "mixed": [3, 5, 1, 7, 2, 6]}


def _engine(family, **kw):
    cfg = dict(max_seqs=2, page_size=8, max_pages_per_seq=16,
               prefill_buckets=(32,), decode_steps=FULL, max_logprobs=3)
    cfg.update(kw)
    return LLMEngine(*tiny_family(family), EngineConfig(**cfg))


def _run(eng, *requests):
    """Step the engine until idle; ({request id: [StepOutput]}, arguments
    of the `dispatch_decode` spans the run left in the flight recorder)."""
    fr._ring.clear()    # a bounded ring: a position in it does not last
    for r in requests:
        eng.add_request(r)
    got = {}
    for _ in range(500):
        if not eng.has_work():
            break
        for so in eng.step():
            got.setdefault(so.request_id, []).append(so)
    assert not eng.has_work()
    return got, [e["args"] for e in fr.dump_events()
                 if e.get("kind") == "span"
                 and e["name"] == "ray_tpu.engine.dispatch_decode"]


def _cut(eng, lengths):
    """Make `eng` run windows of `lengths`, over and over, whatever its
    slots and queue say."""
    turn = itertools.cycle(lengths)
    eng._window_steps = lambda: next(turn)


# -- (a) one program, any cut: the same tokens, logprobs and cache ----------
def _cut_run(family, temperature, cut):
    eng = _engine(family)
    _cut(eng, CUTS[cut])
    got, spans = _run(eng, Request(
        "a", _ids(13), max_tokens=25, temperature=temperature, seed=11,
        logprobs=3))
    return (got["a"], [s["steps"] for s in spans],
            [np.asarray(x) for x in jax.tree.leaves(eng.caches)], eng)


@functools.lru_cache(maxsize=None)
def _whole(family, temperature):
    return _cut_run(family, temperature, "whole")[:3]


@pytest.mark.parametrize("cut", ["halves", "mixed"])
@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("family", ["llama", "olmo_hybrid", "jamba"])
def test_any_cut_into_windows_makes_the_same_tokens_and_cache(
        family, temperature, cut):
    want, whole_steps, want_caches = _whole(family, temperature)
    assert whole_steps == [8, 8, 8] and len(want) == 25
    outs, steps, caches, eng = _cut_run(family, temperature, cut)
    assert steps == (CUTS[cut] * 6)[:len(steps)] and sum(steps) == 24
    assert [o.token for o in outs] == [o.token for o in want]
    assert [o.finished for o in outs] == [False] * 24 + [True]
    # the same arithmetic in the same order: equal, not merely close
    assert [o.logprob for o in outs] == [o.logprob for o in want]
    assert [o.top_logprobs for o in outs] == [o.top_logprobs for o in want]
    # K/V pages and, where the family has them, the state pools that every
    # step updates in place
    assert len(caches) == len(want_caches)
    for got, ref in zip(caches, want_caches):
        np.testing.assert_array_equal(got, ref)
    # one decode program, whatever lengths it ran
    assert list(eng._decode_fns) == [(False, True)]
    assert eng._decode_fns[(False, True)]._cache_size() == 1
    assert eng.programs_report()["retraced"] == 0


# -- (b) the rule -----------------------------------------------------------
@pytest.mark.parametrize("decode_steps,half", [(8, 4), (4, 2), (3, 1),
                                               (1, 1), (0, 1)])
def test_half_a_window_while_a_slot_is_free_and_never_none(decode_steps,
                                                           half):
    full = max(1, decode_steps)
    eng = _engine("llama", decode_steps=decode_steps)
    assert eng._window_steps() == half          # idle: both slots free
    eng.add_request(Request("a", _ids(9), max_tokens=40))
    early = eng.step()
    assert len(eng._free_slots) == 1 and eng._window_steps() == half
    eng.add_request(Request("b", _ids(9, seed=3), max_tokens=40))
    early += eng.step()
    # no slot: nobody can be admitted before the window ends
    assert not eng._free_slots and eng._window_steps() == full
    eng.add_request(Request("c", _ids(9, seed=4), max_tokens=4))
    assert eng._window_steps() == full          # it waits for a slot
    got, spans = _run(eng)
    for so in early:
        got[so.request_id].append(so)
    assert {r: len(v) for r, v in got.items()} == {"a": 40, "b": 40, "c": 4}
    assert {s["steps"] for s in spans} == {half, full}
    assert all(s["steps"] == (half if s["free_slots"] else full)
               for s in spans)
    assert eng._window_steps() == half


def test_full_window_while_the_queue_waits_for_pages():
    # 7 pages of 8 tokens: "a" holds 5 of them at its admission, so "b"
    # (4 pages and one more token) waits though a slot is free
    eng = _engine("llama", num_pages=7, enable_prefix_cache=False)
    fr._ring.clear()
    eng.add_request(Request("a", _ids(30), max_tokens=9))
    eng.add_request(Request("b", _ids(32, seed=3), max_tokens=3))
    out = eng.step()
    assert [o.request_id for o in out] == ["a"]
    assert eng._free_slots and len(eng.waiting) == 1
    assert eng._window_steps() == FULL
    first = [e["args"] for e in fr.dump_events()
             if e.get("name") == "ray_tpu.engine.dispatch_decode"]
    assert [(s["steps"], s["free_slots"]) for s in first] == [(FULL, 1)]
    # a request that would fit is no reason for a long window
    eng.waiting[0].prompt_ids = _ids(4)
    assert eng._window_steps() == FULL // 2
    eng.waiting[0].prompt_ids = _ids(32, seed=3)
    got, _ = _run(eng)
    assert len(got["a"]) == 8 and len(got["b"]) == 3


# -- (c) a finish inside a short chained window -----------------------------
def test_finish_inside_a_short_chained_window_leaves_the_chain_whole():
    alone, _ = _run(_engine("llama", max_seqs=3),
                    Request("a", _ids(13), max_tokens=30, logprobs=2))
    eng = _engine("llama", max_seqs=3)
    # "b": prefill's token, a window of four, two of the chained window
    got, spans = _run(eng, Request("a", _ids(13), max_tokens=30, logprobs=2),
                      Request("b", _ids(9, seed=5), max_tokens=7))
    assert [s["steps"] for s in spans] == [4] * len(spans)
    # the window chained behind the one "b" ends in is on the device by the
    # time the host sees the finish, with b's row: the next is chained off
    # it all the same, without that row
    assert [(s["chained"], s["active"], s["across"]) for s in spans[:5]] == [
        (False, 2, "none"), (True, 2, "none"), (True, 2, "none"),
        (True, 1, "finish"), (True, 1, "none")]
    assert len(got["b"]) == 7 and got["b"][-1].finished
    assert sorted(eng._free_slots) == [0, 1, 2]
    # "a" went on from its row on the device, which b's end did not touch
    assert [o.token for o in got["a"]] == [o.token for o in alone["a"]]
    assert [o.logprob for o in got["a"]] == pytest.approx(
        [o.logprob for o in alone["a"]], abs=1e-5)
    # and the freed slot serves the next request as a fresh engine would
    again, _ = _run(eng, Request("c", _ids(9, seed=5), max_tokens=7))
    assert [o.token for o in again["c"]] == [o.token for o in got["b"]]


# -- (d) pages cover two chained windows ------------------------------------
@pytest.mark.parametrize("max_seqs,steps", [(1, FULL), (2, FULL // 2)])
def test_pages_cover_the_window_in_flight_and_the_one_chained(max_seqs,
                                                              steps):
    eng = _engine("llama", max_seqs=max_seqs, page_size=4,
                  max_pages_per_seq=32)
    seen = []
    dispatch = eng._dispatch_window

    def checked(last=None, lens=None, **kw):
        ahead = 0 if eng._inflight is None else eng._inflight.steps
        window = dispatch(last, lens, **kw)
        for slot in window.slots:
            room = len(eng.allocator.slot_pages[slot]) * 4
            written = int(eng.seq_lens[slot]) + ahead + window.steps
            assert written <= room, (slot, written, room)
            seen.append((last is not None, window.steps))
        return window

    eng._dispatch_window = checked
    got, _ = _run(eng, Request("a", _ids(13), max_tokens=42))
    assert len(got["a"]) == 42
    assert {s for _, s in seen} == {steps} and {c for c, _ in seen} == {
        True, False}
    reference, _ = _run(_engine("llama", max_seqs=max_seqs, page_size=16),
                        Request("a", _ids(13), max_tokens=42))
    assert [o.token for o in got["a"]] == [o.token for o in reference["a"]]


# -- (e) block generation keeps its window ----------------------------------
def test_block_family_runs_whole_windows_with_a_slot_free():
    eng = _engine("sdar_moe", max_pages_per_seq=8, prefill_buckets=(16, 32))
    assert eng._free_slots and eng._window_steps() == FULL
    got, spans = _run(eng, Request("a", _ids(14), max_tokens=21))
    assert len(got["a"]) == 21 and len(spans) >= 3
    assert all(s["steps"] == FULL and s["free_slots"] == 1
               and s["fused_commits"] == FULL // 4 for s in spans)
