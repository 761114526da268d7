"""Metric arithmetic on a hand-made event list: bursts of 8, an early stop,
a failed request."""

import math

import pytest

from benchmark import metrics
from benchmark.metrics import Rec


def _burst_times(first, n, window=0.080):
    """n token events: the first alone, then bursts of 8 every `window`."""
    out = [first]
    t = first
    while len(out) < n:
        t += window
        out.extend([t] * min(8, n - len(out)))
    return out


@pytest.fixture
def recs():
    full = Rec(0, due_s=0.0, sent_s=0.001, want_tokens=17,
               events_s=_burst_times(0.100, 17), done_s=0.27, finish="stop")
    late = Rec(1, due_s=1.0, sent_s=1.250, want_tokens=9,
               events_s=_burst_times(1.400, 9), done_s=1.5, finish="stop")
    early = Rec(2, due_s=2.0, sent_s=2.0, want_tokens=64,
                events_s=_burst_times(2.050, 5), done_s=2.2, finish="stop")
    failed = Rec(3, due_s=3.0, sent_s=3.0, want_tokens=16,
                 error="HTTP 429 text/plain")
    return [full, late, early, failed]


def test_ttft_counts_from_the_due_time_and_failures_as_the_window(recs):
    got = metrics.ttft_ms(recs, window_s=10.0)
    assert got == pytest.approx([100.0, 400.0, 50.0, 10_000.0])
    assert metrics.percentile(got, 95) == pytest.approx(10_000.0)
    assert metrics.percentile(got, 50) == pytest.approx(100.0)


def test_tpot_is_per_request_not_per_gap(recs):
    got = metrics.tpot_ms(recs)
    # 17 tokens: first at 0.100, last burst at 0.260 -> 160 ms / 16
    assert got[0] == pytest.approx(10.0)
    # 9 tokens: first, then one burst of 8 after 80 ms -> 80 / 8
    assert got[1] == pytest.approx(10.0)
    assert got[2] == pytest.approx(80.0 / 4)
    assert len(got) == 3                      # the failed one has none
    assert len(metrics.tpot_ms(recs, min_tokens=8)) == 2
    # cut at a time: only the events up to it count
    assert metrics.tpot_ms(recs[:1], until_s=0.2)[0] == pytest.approx(
        80.0 / 8)


def test_tokens_are_counted_inside_the_window_only(recs):
    assert metrics.tokens_in_window(recs, 10.0) == 17 + 9 + 5
    assert metrics.tokens_in_window(recs, 1.45) == 17 + 1
    assert metrics.out_tok_per_s(recs, 2.0) == pytest.approx((17 + 9) / 2.0)


def test_generator_lateness_and_summary(recs):
    late = metrics.gen_late_ms(recs)
    assert late == pytest.approx([1.0, 250.0, 0.0, 0.0])
    s = metrics.summarize(recs, 10.0)
    assert s["attempted"] == 4 and s["failed"] == 1
    assert s["fewer_events_than_asked"] == 1 and s["token_events"] == 31


def test_attainment_needs_both_limits(recs):
    assert metrics.attainment(recs, 10.0, 1000.0, 50.0) == 0.75
    assert metrics.attainment(recs, 10.0, 200.0, 50.0) == 0.5
    assert metrics.attainment(recs, 10.0, 1000.0, 15.0) == 0.5
    assert metrics.attainment([], 10.0, 1.0, 1.0) == 0.0


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert metrics.percentile(values, 95) == 95
    assert metrics.percentile(values, 100) == 100
    assert metrics.percentile([5.0], 95) == 5.0
    with pytest.raises(ValueError):
        metrics.percentile([], 50)
    assert math.isnan(Rec(0, 0.0).sent_s)
