"""The chain of decode windows outlives a finish and an admission
(`LLMEngine._step`, `_admit`): the next window is queued before the host
reads the last one, off the device's last tokens and lengths, into which an
admission's prefills scatter their rows. Whatever the schedule, every
request reads what `pipeline_dispatch=False` gives it, a window's stale row
never reaches the request that took the slot meanwhile, and a
block-generating model keeps its drain at an admission. Tiny models, float32
on the CPU; an engine's decode programs are compiled once for each (family,
config) of the module (`engine_sharing`)."""

import functools

import pytest

pytest.importorskip("jax")

from engine_sharing import prompt_ids as _ids  # noqa: E402
from engine_sharing import share_decode_programs, tiny_family  # noqa: E402
from ray_tpu._private import flight_recorder as fr  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402

DISPATCH = "ray_tpu.engine.dispatch_decode"
WAIT = "ray_tpu.engine.wait_tokens"
TOKEN_FAMILIES = ["llama", "olmo_hybrid", "jamba"]


def _engine(family, **kw):
    cfg = dict(max_seqs=3, page_size=8, max_pages_per_seq=16,
               prefill_buckets=(32,), decode_steps=8, max_logprobs=3)
    cfg.update(kw)
    return share_decode_programs(
        LLMEngine(*tiny_family(family), EngineConfig(**cfg)))


def _drive(eng, schedule, watch=None):
    """Step `eng` until idle, handing it each (step, request) of `schedule`
    before that step. Returns ({request id: [StepOutput]}, the run's spans)."""
    fr._ring.clear()    # a bounded ring: a position in it does not last
    schedule = sorted(schedule, key=lambda item: item[0])
    got, step = {}, 0
    while schedule or eng.has_work():
        while schedule and schedule[0][0] <= step:
            eng.add_request(schedule.pop(0)[1])
        for so in eng.step():
            got.setdefault(so.request_id, []).append(so)
        if watch is not None:
            watch(eng)
        step += 1
        assert step < 500
    assert eng._inflight is None and not eng.running
    return got, [e for e in fr.dump_events() if e.get("kind") == "span"]


def _args(spans, name):
    return [e["args"] for e in spans if e["name"] == name]


def _same(got, want):
    assert set(got) == set(want)
    for rid in want:
        assert [o.token for o in got[rid]] == [o.token for o in want[rid]]
        assert [o.finished for o in got[rid]] == [
            o.finished for o in want[rid]]
        # the same rows through the same programs: equal, not merely close
        assert [o.logprob for o in got[rid]] == [
            o.logprob for o in want[rid]]
        assert [o.top_logprobs for o in got[rid]] == [
            o.top_logprobs for o in want[rid]]


def _schedule():
    """Three slots. "b" ends in the middle of a window with nobody waiting
    (a finish alone); "c" is admitted while windows are in flight; "d" ends
    on its first token; "e" and "f" take slots others left, one of them a
    sampled stream; "g" ends on a stop token, which no count foresees."""
    return [
        (0, Request("a", _ids(13), max_tokens=46, logprobs=3,
                    temperature=0.8, seed=3)),
        (0, Request("b", _ids(9, seed=5), max_tokens=7, logprobs=3)),
        (5, Request("c", _ids(11, seed=6), max_tokens=10, logprobs=3,
                    temperature=0.8, top_k=20, seed=4)),
        (6, Request("d", _ids(20, seed=7), max_tokens=1, logprobs=3)),
        (6, Request("e", _ids(20, seed=8), max_tokens=12, logprobs=3)),
        (7, Request("f", _ids(5, seed=9), max_tokens=9, logprobs=3)),
        (8, Request("g", _ids(6, seed=10), max_tokens=30, logprobs=3)),
    ]


@functools.lru_cache(maxsize=None)
def _unpipelined(family):
    """The reference: every window read before the next is dispatched. "g"
    is then run again to stop on a token it made in the middle of a window."""
    first, _ = _drive(_engine(family, pipeline_dispatch=False), _schedule())
    made = [o.token for o in first["g"]]
    stop = next(t for i, t in enumerate(made) if i > 1 and t not in made[:i])
    got, spans = _drive(_engine(family, pipeline_dispatch=False),
                        _stopping(stop))
    assert not any(a["chained"] for a in _args(spans, DISPATCH))
    assert {a["why"] for a in _args(spans, WAIT)} == {"unpipelined"}
    return stop, got


def _stopping(stop):
    schedule = _schedule()
    schedule[-1][1].stop_token = stop
    return schedule


def _chained_while_busy(spans):
    """A busy stretch opens with an unchained dispatch and closes with a
    window read with none queued behind it, because nothing ran or every
    request ended inside it: every other dispatch is chained, every other
    read has the next window behind it, and every window is read once."""
    decode = _args(spans, DISPATCH)
    whys = [a["why"] for a in _args(spans, WAIT)]
    assert set(whys) <= {"chained", "all_finishing", "idle"}
    assert not decode[0]["chained"] and len(whys) == len(decode)
    stretches = sum(not a["chained"] for a in decode)
    assert stretches == len(whys) - whys.count("chained")
    assert all(a["across"] == "none" for a in decode if not a["chained"])
    # a stretch reopens only after one has closed
    order = sorted((e for e in spans if e["name"] in (DISPATCH, WAIT)),
                   key=lambda e: e["ts"])
    open_ = False
    for e in order:
        if e["name"] == DISPATCH:
            assert e["args"]["chained"] == open_
            open_ = True
        elif e["args"]["why"] != "chained":
            open_ = False
    return decode, whys


# -- (i) finishes and admissions: what the unpipelined run gives -----------
@pytest.mark.parametrize("family", TOKEN_FAMILIES)
def test_finishes_and_admissions_read_as_the_unpipelined_run(family):
    stop, want = _unpipelined(family)
    assert [len(want[r]) for r in "abcdef"] == [46, 7, 10, 1, 12, 9]
    assert 2 < len(want["g"]) < 30 and want["g"][-1].token == stop
    eng = _engine(family)
    got, spans = _drive(eng, _stopping(stop))
    _same(got, want)
    # the chain was put to both tests, and never broken while anything ran
    decode = _args(spans, DISPATCH)
    assert {"finish", "admission", "none"} <= {a["across"] for a in decode}
    _chained_while_busy(spans)
    assert eng.windows_report() == {
        "unchained": sum(not a["chained"] for a in decode),
        **{k: sum(a["across"] == k and a["chained"] for a in decode)
           for k in ("none", "finish", "admission")}}
    assert sorted(eng._free_slots) == [0, 1, 2]
    assert not any(eng.allocator.slot_pages[slot] for slot in range(3))
    # one prefill program a shape, with a window in flight or without
    assert eng.programs_report()["retraced"] == 0
    assert all(fn._cache_size() == 1 for fn in eng._prefill_fns.values())


# -- (ii) a slot's next request never sees the window dispatched before it --
@pytest.mark.parametrize("first_only", [False, True])
@pytest.mark.parametrize("family", TOKEN_FAMILIES)
def test_a_reused_slot_gets_no_token_of_the_window_in_flight(family,
                                                             first_only):
    """One slot beside "a"'s. "b" ends inside window W; W+1 was dispatched
    with its row. "c" waits and takes the slot while W+1 is in flight: W+1
    holds tokens under c's slot that are b's. With `first_only`, c ends on
    its first token, after the window behind its prefill was dispatched with
    its row, and "d" takes the slot in turn."""
    requests = lambda: [
        Request("a", _ids(13), max_tokens=40, logprobs=3),
        Request("b", _ids(9, seed=5), max_tokens=7, logprobs=3),
        Request("c", _ids(11, seed=6), max_tokens=1 if first_only else 10,
                logprobs=3),
        Request("d", _ids(7, seed=7), max_tokens=6, logprobs=3)]
    want, _ = _drive(_engine(family, max_seqs=2, pipeline_dispatch=False),
                     [(0, r) for r in requests()])
    eng = _engine(family, max_seqs=2)
    stale = []  # (request the row was dispatched for, request in the slot)
    emit = eng._emit_window

    def spy(toks, lp, slots, out):
        stale.extend((req.request_id, eng.running[slot].request_id)
                     for slot, req in slots.items()
                     if slot in eng.running and eng.running[slot] is not req)
        return emit(toks, lp, slots, out)

    eng._emit_window = spy
    reqs = requests()
    got, spans = _drive(eng, [(0, r) for r in reqs])
    _same(got, want)
    assert [r.generated for r in reqs] == [40, 7, 1 if first_only else 10, 6]
    # the hazard did arise: a window read while its row's slot held the next
    # ("c" that ends on its first token has left b's slot by the time b's
    # window is read, and its own row meets "d")
    assert (("c", "d") if first_only else ("b", "c")) in stale
    # and every token emitted was counted once
    emitted = sum(a["tokens"] for a in _args(spans, "ray_tpu.engine.emit"))
    assert emitted == sum(len(v) - 1 for v in got.values())


# -- (iii) the freed pages go to the admission, prefix sharing on ----------
def test_prefix_pages_of_a_finished_request_serve_the_admission_behind_it():
    """Llama, one slot beside "a"'s: "b" ends inside a window, "c" opens
    with b's first two pages and is admitted while the window that still
    names b's pages is in flight: it shares b's prompt pages, which decode
    never writes, and takes its other pages from the free list."""
    head = _ids(16, seed=11)
    requests = lambda: [
        Request("a", _ids(13), max_tokens=40, logprobs=3),
        Request("b", head + _ids(5, seed=12), max_tokens=7, logprobs=3),
        Request("c", head + _ids(9, seed=13), max_tokens=12, logprobs=3)]
    kw = dict(max_seqs=2, enable_prefix_cache=True)
    want, _ = _drive(_engine("llama", pipeline_dispatch=False, **kw),
                     [(0, r) for r in requests()])
    alone, _ = _drive(_engine("llama", **kw), [(0, requests()[2])])
    eng = _engine("llama", **kw)
    pages = {}

    def watch(eng):
        for slot, req in eng.running.items():
            pages.setdefault(req.request_id, (slot, list(
                eng.allocator.slot_pages[slot])))

    got, spans = _drive(eng, [(0, r) for r in requests()], watch)
    _same(got, want)
    assert [o.token for o in got["c"]] == [o.token for o in alone["c"]]
    prefills = _args(spans, "ray_tpu.engine.prefill_dispatch")
    assert [p["cached_tokens"] for p in prefills] == [0, 16]
    assert pages["c"][0] == pages["b"][0]            # b's slot
    assert pages["c"][1][:2] == pages["b"][1][:2]    # b's prompt pages
    assert set(pages["c"][1][2:]) & set(pages["b"][1][2:])  # and freed ones
    decode = _args(spans, DISPATCH)
    assert "admission" in {a["across"] for a in decode}
    assert all(a["chained"] for a in decode[1:])


# -- (iv) what a trace of a busy stretch says -------------------------------
@pytest.mark.parametrize("family", TOKEN_FAMILIES)
def test_no_window_is_drained_while_anything_runs(family):
    eng = _engine(family)
    _, spans = _drive(eng, _schedule())
    decode, whys = _chained_while_busy(spans)
    assert "admitted" not in whys and "finished_in_chain" not in whys
    assert {"finish", "admission"} <= {a["across"] for a in decode}
    assert whys.count("chained") > 3 * (len(whys) - whys.count("chained"))
    # the admission's window is dispatched inside its `admit` span, before
    # the first tokens are read
    by_name = lambda name: [e for e in spans if e["name"] == name]
    start = lambda e: e["ts"] * 1e6
    end = lambda e: e["ts"] * 1e6 + e["dur_us"]
    inside = lambda e, outer: (start(outer) <= start(e)
                               and end(e) <= end(outer))
    admits = [e for e in by_name("ray_tpu.engine.admit")
              if e["args"]["admitted"]]
    syncs = by_name("ray_tpu.engine.prefill_sync")
    for d in by_name(DISPATCH):
        if d["args"]["across"] != "admission":
            assert not any(inside(d, a) for a in admits)
            continue
        admit = next(a for a in admits if inside(d, a))
        sync = next(s for s in syncs if inside(s, admit))
        assert end(d) <= start(sync)
        assert d["args"]["active"] >= admit["args"]["admitted"]


def test_an_idle_engine_holds_no_window():
    """Every request ends on a stop token inside a window with the next one
    queued: nothing runs, and the queued window is read at once (`idle`), so
    whoever waits for `_inflight` to clear does not wait for work that will
    not come."""
    probe, _ = _drive(_engine("llama"), [
        (0, Request("a", _ids(13), max_tokens=20))])
    made = [o.token for o in probe["a"]]
    at = next(i for i in range(4, 20) if made[i] not in made[:i])
    stop = made[at]
    eng = _engine("llama")
    eng.add_request(Request("a", _ids(13), max_tokens=20, stop_token=stop))
    fr._ring.clear()
    outs = []
    while eng.has_work():
        outs += eng.step()
    assert len(outs) == at + 1 and outs[-1].finished
    assert eng._inflight is None
    whys = [e["args"]["why"] for e in fr.dump_events()
            if e.get("name") == WAIT]
    assert whys[-1] == "idle" and "finished_in_chain" not in whys


# -- (v) block generation: the finish re-chains, the admission drains -------
def test_block_generation_keeps_the_chain_at_a_finish_and_drains_at_an_admission():
    kw = dict(max_seqs=2, prefill_buckets=(16, 32))
    schedule = lambda: [
        (0, Request("a", _ids(14), max_tokens=60, logprobs=3)),
        (0, Request("b", _ids(9, seed=5), max_tokens=11, logprobs=3)),
        (6, Request("c", _ids(17, seed=6), max_tokens=13, logprobs=3,
                    temperature=0.7, seed=5)),
    ]
    want, _ = _drive(_engine("sdar_moe", pipeline_dispatch=False, **kw),
                     schedule())
    eng = _engine("sdar_moe", **kw)
    got, spans = _drive(eng, schedule())
    _same(got, want)
    assert [len(got[r]) for r in "abc"] == [60, 11, 13]
    decode = _args(spans, DISPATCH)
    whys = [a["why"] for a in _args(spans, WAIT)]
    # "b" ends inside a window while "a" runs on: the chain goes on
    assert "finish" in {a["across"] for a in decode}
    assert "finished_in_chain" not in whys
    # "c" is admitted with a window in flight: drained, and the next window
    # comes from the host's mirrors, which alone hold its first block
    assert whys.count("admitted") == 1
    assert "admission" not in {a["across"] for a in decode}
    unchained = [i for i, a in enumerate(decode) if not a["chained"]]
    assert len(unchained) == 2 and unchained[0] == 0
    assert decode[unchained[1]]["fresh_rows"] == 1
    assert decode[unchained[1]]["active"] == 2
    assert eng.windows_report()["admission"] == 0
    assert eng.windows_report()["finish"] >= 1
