"""Kernels: the Mamba-2 scan kernel's share of its roofline: the larger of the
time the chip's peak HBM bandwidth needs for the bytes a call has to move and
the time its peak bf16 matmul rate needs for the operations it cannot do
without (`families/<family>.py` `ssd_scan_bytes` and `ssd_scan_flops` of the
prompt tokens a prefill dispatch walked: `tokens` of the program's
`ray_tpu.engine.prefill_dispatch` spans, mean over the slice; padding past a
row's last chunk is skipped, not walked), over the call's device time. Both
counts are floors (no padding, the causal half of a chunk's square only), so
the share cannot pass 100. What it leaves out: the decays are an `exp` and
three multiplies for each pair of positions of a chunk and each head, the
vector units' work, for which `peaks.json` has no peak."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.prefill_dispatch"


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "ssd_scan_kernel_ms.py"),
                    "_bench_metric_ssd_scan_kernel_ms").seconds_per_call(obs)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count_bytes = getattr(family, "ssd_scan_bytes", None)
    count_flops = getattr(family, "ssd_scan_flops", None)
    if not peaks or count_bytes is None or count_flops is None:
        return None
    seconds = _kernel_seconds(obs)
    # a slice of four seconds holds a handful of waves: every span counts
    walked = [float(e["stats"]["tokens"])
              for e in program_trace.events(obs, SPAN)
              if "tokens" in e["stats"]]
    if not seconds or not walked:
        return None
    tokens = sum(walked) / len(walked)
    least = max(
        count_bytes(obs["config"], tokens) / peaks["hbm_bytes_per_s"],
        count_flops(obs["config"], tokens) / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
