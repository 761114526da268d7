"""Kernels: the banded flash forward's share of the chip's peak bf16 matmul
rate: the operations one call cannot do without (`families/<family>.py`
`swa_flash_flops`: q k^T and p v for each visible pair of the prompt tokens a
prefill dispatch walked, `tokens` in `nb` rows of the program's
`ray_tpu.engine.prefill_dispatch` spans, mean over the slice; no padding, no
masked part of a block) over the peak rate, over the call's device time. The
kernel multiplies whole blocks (a q block of 512 against three key blocks
where 1,024 keys are visible, and the bucket's padding), which is not
counted: a floor, so the share cannot pass 100."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.prefill_dispatch"


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "swa_flash_kernel_ms.py"),
                    "_bench_metric_swa_flash_kernel_ms").seconds_per_call(obs)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "swa_flash_flops", None)
    if not peaks or count is None:
        return None
    seconds = _kernel_seconds(obs)
    # a slice of four seconds holds a handful of admissions: every span counts
    walked = [count(obs["config"], float(e["stats"]["tokens"]),
                    int(e["stats"]["nb"]))
              for e in program_trace.events(obs, SPAN)
              if "tokens" in e["stats"] and "nb" in e["stats"]]
    if not seconds or not walked:
        return None
    least = sum(walked) / len(walked) / peaks["bf16_flops_per_s"]
    return 100.0 * least / seconds
