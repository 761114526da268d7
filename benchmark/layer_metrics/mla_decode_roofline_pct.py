"""Kernels: the latent decode kernel's share of its roofline: the least time
one call can take, the larger of the bytes it cannot do without
(`families/<family>.py` `mla_decode_bytes`: the cache rows of the tokens the
active rows hold, as they lie in memory) over the chip's peak HBM bandwidth
and of its operations (`mla_decode_flops`: every head's query against a row's
576 values and its weight times the row's 512) over the peak bf16 matmul
rate, over the call's device time. At 121 operations a byte against the
chip's 240 the kernel is memory-bound with little to spare, which is why
both are reckoned. The tokens are `context_tokens` of the program's
`ray_tpu.engine.dispatch_decode` spans (the sum over the active rows of
their lengths at the dispatch), the spans weighted by their token `steps`.
Both are floors: the lengths are the host's at the dispatch (rows grow a
token a step, and a chained window's rows are a window further on), whole
pages are read, the queries and the output are left out; so the share cannot
pass 100."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.dispatch_decode"


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "mla_decode_kernel_us.py"),
                    "_bench_metric_mla_decode_kernel_us").seconds_per_call(obs)


def context_tokens(obs):
    """Mean `context_tokens` a token step, or None where the program's spans
    carry none."""
    stats = [e["stats"] for e in program_trace.events(obs, SPAN)
             if "context_tokens" in e["stats"] and "steps" in e["stats"]]
    steps = sum(float(s["steps"]) for s in stats)
    if len(stats) < program_trace.MIN_EVENTS or not steps:
        return None
    return sum(float(s["context_tokens"]) * float(s["steps"])
               for s in stats) / steps


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count_bytes = getattr(family, "mla_decode_bytes", None)
    count_flops = getattr(family, "mla_decode_flops", None)
    if not peaks or count_bytes is None or count_flops is None:
        return None
    seconds, tokens = _kernel_seconds(obs), context_tokens(obs)
    if not seconds or not tokens:
        return None
    least = max(
        count_bytes(obs["config"], tokens) / peaks["hbm_bytes_per_s"],
        count_flops(obs["config"], tokens) / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
