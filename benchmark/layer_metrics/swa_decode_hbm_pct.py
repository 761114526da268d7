"""Kernels: the windowed decode kernel's share of its roofline. The kernel is
bound by memory: the bytes one call cannot do without (`families/<family>.py`
`swa_decode_bytes`: K and V of the tokens inside the window, `window_tokens` of
the program's `ray_tpu.engine.dispatch_decode` spans = the sum over the active
rows of min(length, window), the spans weighted by their token `steps`) over
the chip's peak HBM bandwidth, over the call's device time. A floor: the
lengths are the host's at the dispatch (a chained window's rows are further
on), whole pages are read at both ends of a window, and the queries and the
output are left out; so the share cannot pass 100."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.dispatch_decode"


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "swa_decode_kernel_us.py"),
                    "_bench_metric_swa_decode_kernel_us").seconds_per_call(obs)


def window_tokens(obs):
    """Mean `window_tokens` a token step, or None where the program's spans
    carry none."""
    stats = [e["stats"] for e in program_trace.events(obs, SPAN)
             if "window_tokens" in e["stats"] and "steps" in e["stats"]]
    steps = sum(float(s["steps"]) for s in stats)
    if len(stats) < program_trace.MIN_EVENTS or not steps:
        return None
    return sum(float(s["window_tokens"]) * float(s["steps"])
               for s in stats) / steps


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "swa_decode_bytes", None)
    if not peaks or count is None:
        return None
    seconds, tokens = _kernel_seconds(obs), window_tokens(obs)
    if not seconds or not tokens:
        return None
    least = count(obs["config"], tokens) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
