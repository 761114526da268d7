"""Metric arithmetic on what the client recorded. No I/O, no clock.

A request's record (`Rec`) holds times on the client's monotonic clock,
relative to the window's start: when it was due (open loop) or sent, and
one entry per SSE event that carried text. One such event is one token
(tokenizer_gen.py); tokens come in bursts of the engine's `decode_steps`."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional


@dataclasses.dataclass
class Rec:
    index: int
    due_s: float                 # when it should have been sent
    sent_s: float = math.nan     # when it was
    events_s: List[float] = dataclasses.field(default_factory=list)
    done_s: Optional[float] = None   # [DONE] seen
    finish: Optional[str] = None     # finish_reason of the last chunk
    want_tokens: int = 0
    replaced: int = 0            # U+FFFD characters received (lone bytes)
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.done_s is not None


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) — the value such that at
    least q% of the samples are <= it. Raises on an empty list."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def ttft_ms(recs: List[Rec], window_s: float) -> List[float]:
    """First token event minus DUE time, per request due in the window. A
    request that failed, was refused or produced no token counts as the
    window's length."""
    out = []
    for r in recs:
        if r.error is not None or not r.events_s:
            out.append(window_s * 1e3)
        else:
            out.append((r.events_s[0] - r.due_s) * 1e3)
    return out


def tpot_ms(recs: List[Rec], until_s: Optional[float] = None,
            min_tokens: int = 2) -> List[float]:
    """(last token event - first) / (tokens - 1) per request, over the
    events received up to `until_s` (all, if None). Per request and not per
    gap: tokens arrive in bursts of `decode_steps`, so a percentile of gaps
    would be a property of that constant."""
    out = []
    for r in recs:
        ev = [t for t in r.events_s if until_s is None or t <= until_s]
        if r.error is None and len(ev) >= max(2, min_tokens):
            out.append((ev[-1] - ev[0]) / (len(ev) - 1) * 1e3)
    return out


def tokens_in_window(recs: List[Rec], window_s: float) -> int:
    return sum(1 for r in recs for t in r.events_s if 0.0 <= t <= window_s)


def out_tok_per_s(recs: List[Rec], window_s: float) -> float:
    """Output tokens received inside the window over its length."""
    return tokens_in_window(recs, window_s) / window_s


def gen_late_ms(recs: List[Rec]) -> List[float]:
    return [(r.sent_s - r.due_s) * 1e3 for r in recs
            if not math.isnan(r.sent_s)]


def attainment(recs: List[Rec], window_s: float, ttft_limit_ms: float,
               tpot_limit_ms: float) -> float:
    """Share of requests that met both limits (a failed one meets none)."""
    if not recs:
        return 0.0
    good = 0
    for r, first in zip(recs, ttft_ms(recs, window_s)):
        gap = tpot_ms([r])
        if (r.ok and first <= ttft_limit_ms
                and (not gap or gap[0] <= tpot_limit_ms)):
            good += 1
    return good / len(recs)


def summarize(recs: List[Rec], window_s: float) -> Dict[str, float]:
    """Counts the driver prints before its last line."""
    failed = [r for r in recs if not r.ok]
    fewer = [r for r in recs if r.ok and len(r.events_s) < r.want_tokens]
    return {"attempted": len(recs), "failed": len(failed),
            # a silent id (tokenizer_gen.py) or an end on the stop id
            "fewer_events_than_asked": len(fewer),
            "token_events": sum(len(r.events_s) for r in recs),
            "token_events_in_window": tokens_in_window(recs, window_s),
            "replaced_chars": sum(r.replaced for r in recs)}
