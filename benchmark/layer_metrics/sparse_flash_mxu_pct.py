"""Kernels: the block-sparse flash forward's share of the chip's peak bf16
matmul rate: the operations one call cannot do without
(`families/<family>.py` `sparse_flash_flops`: q k^T and p v for each (query,
key) pair a query attends to, t + 1 keys a position under `dense_len` and the
chosen blocks' keys past it; a prefill dispatch of `tokens` in `nb` rows, from
the program's `ray_tpu.engine.prefill_dispatch` spans, makes a call a sparse
layer and row, its rows taken as equal; the mean over the slice's rows) over
the peak rate, over the call's device time. The kernel multiplies whole
tiles (the bucket's padding, the masked half of a diagonal tile, and every
block of a tile that some query of it chose, for all its queries), which is
not counted: a floor, so the share cannot pass 100."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

SPAN = "ray_tpu.engine.prefill_dispatch"


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "sparse_flash_flops", None)
    if not peaks or count is None:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    seconds = _load_py(os.path.join(here, "sparse_flash_kernel_ms.py"),
                       "_bench_metric_sparse_flash_kernel_ms"
                       ).seconds_per_call(obs)
    waves = [(float(e["stats"]["tokens"]), int(e["stats"]["nb"]))
             for e in program_trace.events(obs, SPAN)
             if "tokens" in e["stats"] and "nb" in e["stats"]]
    rows = sum(nb for _, nb in waves)
    if not seconds or not rows:
        return None
    least = sum(nb * count(obs["config"], tokens, nb)
                for tokens, nb in waves) / rows / peaks["bf16_flops_per_s"]
    return 100.0 * least / seconds
