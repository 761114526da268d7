"""One general traffic generator, driven by a workload file's parameters.

The file alone fixes the work: the multiset of prompt lengths, output
lengths and arrival gaps (a distribution's quantiles at (i + 0.5) / n, not n
random draws) AND their order (one shuffle, seeded by the file's
`order_seed`, default 0). `--seed` chooses every prompt's token ids (and, in
the driver, the model's weights). Measured on the chip (PR 23): with the
order drawn from `--seed`, `ttft_p95_ms` of `chat-steady` read 214-325 ms
across six seeds and the same value twice for the same seed — the order of
arrivals decides the tail — so a run measured its seed, not the program.

Length distributions (`{"dist": ...}`):
  fixed      {"value": v}
  uniform    {"min": a, "max": b}
  lognormal  {"median": m, "sigma": s, "min": a, "max": b}   (clipped)
  choice     {"values": [...], "weights": [...]}
Arrivals (`"arrivals"`, open loop):
  {"process": "exponential", "rate_per_s": r}          Poisson-like
  {"process": "bursts", "rate_per_s": r, "burst": k}   k requests arrive
      together; gaps between bursts exponential at r / k
Closed loop: `"clients": n` callers that each wait for the reply.
Sharing (`"prefix"`, optional): {"groups": g, "len": <dist>} — each request
  starts with its group's tokens, so full pages of it can be shared."""

from __future__ import annotations

import dataclasses
import math
import random
import statistics
from typing import Any, Dict, List, Optional

from benchmark.tokenizer_gen import SPECIALS

# Prompt ids are drawn from the merged part of the vocabulary: no byte
# fragments, no specials.
FIRST_TEXT_ID = 256


@dataclasses.dataclass
class Req:
    index: int
    due_s: Optional[float]      # None in a closed loop: sent when a client frees
    prompt: List[int]
    max_tokens: int


def quantiles(dist: Dict[str, Any], n: int) -> List[float]:
    """The n-point multiset of `dist`: its quantiles at (i + 0.5) / n."""
    kind = dist["dist"]
    ps = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        return [float(dist["value"])] * n
    if kind == "uniform":
        a, b = float(dist["min"]), float(dist["max"])
        return [a + (b - a) * p for p in ps]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        normal = statistics.NormalDist()
        return [min(max(math.exp(mu + sigma * normal.inv_cdf(p)),
                        dist["min"]), dist["max"]) for p in ps]
    if kind == "exponential":
        return [-math.log(1.0 - p) * dist["mean"] for p in ps]
    if kind == "choice":
        total = float(sum(dist["weights"]))
        out, acc, j = [], dist["weights"][0] / total, 0
        for p in ps:
            while p > acc and j < len(dist["values"]) - 1:
                j += 1
                acc += dist["weights"][j] / total
            out.append(float(dist["values"][j]))
        return out
    raise ValueError(f"unknown distribution {kind!r}")


def _shuffled(values: List[Any], rng: random.Random) -> List[Any]:
    values = list(values)
    rng.shuffle(values)
    return values


def due_times(arrivals: Dict[str, Any], seconds: float,
              rng: random.Random) -> List[float]:
    """Due times in [0, seconds): a fixed multiset of gaps whose sum is
    `seconds`, in `rng`'s order; the first request is due at 0."""
    burst = int(arrivals.get("burst", 1)) if \
        arrivals["process"] == "bursts" else 1
    if arrivals["process"] not in ("exponential", "bursts"):
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    n_groups = max(1, round(arrivals["rate_per_s"] * seconds / burst))
    gaps = quantiles({"dist": "exponential", "mean": 1.0}, n_groups)
    scale = seconds / sum(gaps)
    gaps = _shuffled([g * scale for g in gaps], rng)
    out, t = [], 0.0
    for g in gaps:
        out.extend([t] * burst)
        t += g
    return out


def text_ids(rng: random.Random, n: int, vocab: int) -> List[int]:
    hi = vocab - len(SPECIALS)
    return [rng.randrange(FIRST_TEXT_ID, hi) for _ in range(n)]


def requests(traffic: Dict[str, Any], vocab_size: int, seed: int,
             seconds: float) -> List[Req]:
    """The run's requests. Open loop: one per arrival in the window, with
    its due time. Closed loop: `clients * rounds` requests without due
    times, which the clients take in order and cycle through."""
    rng = random.Random(seed)                       # token ids
    order = random.Random(int(traffic.get("order_seed", 0)))   # the schedule
    if "clients" in traffic:
        n = int(traffic["clients"]) * int(traffic.get("rounds", 8))
        dues: List[Optional[float]] = [None] * n
    else:
        dues = list(due_times(traffic["arrivals"], seconds, order))
        n = len(dues)
    p_lens = _shuffled(quantiles(traffic["prompt_len"], n), order)
    o_lens = _shuffled(quantiles(traffic["output_len"], n), order)
    prefix = traffic.get("prefix")
    groups: List[List[int]] = []
    if prefix:
        g_lens = quantiles(prefix["len"], int(prefix["groups"]))
        groups = [text_ids(rng, int(round(x)), vocab_size) for x in g_lens]
    out = []
    for i in range(n):
        plen = max(1, int(round(p_lens[i])))
        head = groups[order.randrange(len(groups))][:plen - 1] \
            if groups else []
        out.append(Req(i, dues[i],
                       head + text_ids(rng, plen - len(head), vocab_size),
                       max(1, int(round(o_lens[i])))))
    return out


def buckets_used(traffic: Dict[str, Any], buckets: List[int]) -> List[int]:
    """Prefill buckets the traffic's prompt lengths can fall into (prompts
    that share a cached prefix prefill only their suffix, so every bucket
    up to the longest prompt's counts when `prefix` is set)."""
    lens = quantiles(traffic["prompt_len"], 1001)
    lo = 1 if traffic.get("prefix") else int(round(min(lens)))
    hi = int(round(max(lens)))
    used = []
    for prev, b in zip([0] + list(buckets), buckets):
        if prev < hi and b >= lo:
            used.append(b)
    return used
