"""Kernels: latent attention's flash forward's share of the chip's peak bf16
matmul rate: the operations one call cannot do without (`families/<family>.py`
`mla_flash_flops`: q k^T over 192 and p v over 128 for each visible pair of
one row's prompt tokens; a prefill dispatch of `tokens` in `nb` rows, from the
program's `ray_tpu.engine.prefill_dispatch` spans, makes a call a row and
layer, its rows taken as equal, which gives the fewest pairs any split of the
tokens does; the mean over the slice's rows) over the peak rate, over the
call's device time. The kernel multiplies whole blocks (the masked half of a
diagonal block, and the bucket's padding), which is not counted: a floor, so
the share cannot pass 100."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.prefill_dispatch"


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "mla_flash_kernel_ms.py"),
                    "_bench_metric_mla_flash_kernel_ms").seconds_per_call(obs)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "mla_flash_flops", None)
    if not peaks or count is None:
        return None
    seconds = _kernel_seconds(obs)
    # a slice of four seconds holds a handful of admissions: every span counts
    waves = [(float(e["stats"]["tokens"]), int(e["stats"]["nb"]))
             for e in program_trace.events(obs, SPAN)
             if "tokens" in e["stats"] and "nb" in e["stats"]]
    rows = sum(nb for _, nb in waves)
    if not seconds or not rows:
        return None
    least = sum(nb * count(obs["config"], tokens, nb)
                for tokens, nb in waves) / rows / peaks["bf16_flops_per_s"]
    return 100.0 * least / seconds
