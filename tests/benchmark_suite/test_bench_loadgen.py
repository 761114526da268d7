"""The traffic generator: the same work for every seed, in another order."""

import json
import os

import pytest

from bench_helpers import REPO
from benchmark import loadgen


def _traffic(name):
    with open(os.path.join(REPO, "benchmark", "workloads",
                           name + ".json")) as f:
        return json.load(f)


CHAT = _traffic("chat-steady")
HEAVY = _traffic("decode-heavy")


def test_same_seed_same_requests():
    a = loadgen.requests(CHAT, 32768, 2 ** 31 + 5, 40)
    b = loadgen.requests(CHAT, 32768, 2 ** 31 + 5, 40)
    assert [(r.due_s, r.prompt, r.max_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_tokens) for r in b]


def test_other_seed_same_schedule_other_tokens():
    """Sizes and times come from the file alone; the seed picks the ids."""
    a = loadgen.requests(CHAT, 32768, 1, 40)
    b = loadgen.requests(CHAT, 32768, 2, 40)
    assert len(a) == len(b) == round(CHAT["arrivals"]["rate_per_s"] * 40)
    assert [(r.due_s, len(r.prompt), r.max_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_tokens) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    # the gaps are the exponential quantiles, each once (one is left out:
    # the gap after the last request)
    n = len(a)
    full = loadgen.quantiles({"dist": "exponential", "mean": 1.0}, n)
    left = sorted(g * 40.0 / sum(full) for g in full)
    for g in sorted(y.due_s - x.due_s for x, y in zip(a, a[1:])):
        hit = min(left, key=lambda x: abs(x - g))
        assert abs(hit - g) < 1e-9
        left.remove(hit)
    assert len(left) == 1
    # another `order_seed` in the file is another order of the same work
    c = loadgen.requests(dict(CHAT, order_seed=1), 32768, 1, 40)
    assert sorted(len(r.prompt) for r in c) == sorted(
        len(r.prompt) for r in a)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]


def test_due_times_fill_the_window_in_order():
    reqs = loadgen.requests(CHAT, 32768, 7, 40)
    dues = [r.due_s for r in reqs]
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 40.0
    # exponential gaps: the longest is several times the mean
    longest = max(y - x for x, y in zip(dues, dues[1:]))
    assert longest > 3 * 40.0 / len(dues)


def test_lengths_follow_the_file():
    reqs = loadgen.requests(CHAT, 32768, 3, 40)
    lens = sorted(len(r.prompt) for r in reqs)
    assert lens[0] >= 40 and lens[-1] == 512
    assert 180 <= lens[len(lens) // 2] <= 220        # median 200
    outs = sorted(r.max_tokens for r in reqs)
    assert outs[0] >= 16 and outs[-1] == 256
    assert all(256 <= t < 32768 - 6 for r in reqs for t in r.prompt)


def test_closed_loop_has_no_due_times():
    reqs = loadgen.requests(HEAVY, 32768, 3, 40)
    assert len(reqs) == HEAVY["clients"] * HEAVY["rounds"]
    assert all(r.due_s is None for r in reqs)
    assert min(len(r.prompt) for r in reqs) >= 40
    assert max(len(r.prompt) for r in reqs) <= 128
    assert 512 <= min(r.max_tokens for r in reqs)
    assert max(r.max_tokens for r in reqs) <= 1024


def test_buckets_the_traffic_reaches():
    buckets = [32, 128, 512, 2048]
    assert loadgen.buckets_used(CHAT, buckets) == [128, 512]
    assert loadgen.buckets_used(HEAVY, buckets) == [128]


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "fixed", "value": 7}, 7, 7),
    ({"dist": "uniform", "min": 2, "max": 10}, 2, 10),
    ({"dist": "choice", "values": [1, 100], "weights": [3, 1]}, 1, 100),
    ({"dist": "exponential", "mean": 2.0}, 0, 20),
])
def test_quantile_multisets(dist, lo, hi):
    q = loadgen.quantiles(dist, 100)
    assert len(q) == 100 and lo <= min(q) and max(q) <= hi
    assert q == sorted(q)
    if dist["dist"] == "choice":
        assert q.count(1.0) == 75
    if dist["dist"] == "exponential":
        assert sum(q) / 100 == pytest.approx(2.0, rel=0.02)


def test_bursts_and_shared_prefixes():
    traffic = dict(CHAT, arrivals={"process": "bursts", "rate_per_s": 8.0,
                                   "burst": 4},
                   prefix={"groups": 2, "len": {"dist": "fixed",
                                                "value": 64}})
    reqs = loadgen.requests(traffic, 32768, 1, 10)
    assert len(reqs) == 80
    dues = [r.due_s for r in reqs]
    assert all(len(set(dues[i:i + 4])) == 1 for i in range(0, 80, 4))
    heads = {tuple(r.prompt[:39]) for r in reqs}
    assert len(heads) == 2
    assert loadgen.buckets_used(traffic, [32, 128, 512]) == [32, 128, 512]
