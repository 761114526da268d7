"""Kernels: the selective-scan kernel's share of the memory roofline: the
bytes a call has to move (`families/<family>.py` `ssm_scan_bytes` of the
prompt tokens a prefill dispatch walked: `tokens` of the program's
`ray_tpu.engine.prefill_dispatch` spans, mean over the slice; padding is
skipped, not moved) over the chip's peak HBM bandwidth, over the call's
device time. The kernel is bound by the vector units (an `exp`, two
multiplies and an add for each of 16 states a channel and position), and
`peaks.json` has no peak for them: this share says how far from the memory
roofline the scan stands, and is expected low."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.prefill_dispatch"


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "ssm_scan_kernel_ms.py"),
                    "_bench_metric_ssm_scan_kernel_ms").seconds_per_call(obs)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "ssm_scan_bytes", None)
    if not peaks or count is None:
        return None
    seconds = _kernel_seconds(obs)
    # a slice of four seconds holds a handful of waves: every span counts
    walked = [float(e["stats"]["tokens"])
              for e in program_trace.events(obs, SPAN)
              if "tokens" in e["stats"]]
    if not seconds or not walked:
        return None
    tokens = sum(walked) / len(walked)
    least = count(obs["config"], tokens) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
