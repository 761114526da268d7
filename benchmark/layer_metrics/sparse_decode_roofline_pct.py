"""Kernels: the sparse decode kernel's share of its roofline: the least time
one call can take, the larger of the bytes it cannot do without
(`families/<family>.py` `sparse_decode_bytes`: of every listed page the
listing KV head's keys and values) over the chip's peak HBM bandwidth and of
its operations (`sparse_decode_flops`: a group's 16 query heads against each
listed key and value) over the peak bf16 matmul rate, over the call's device
time. The pages are `pages_selected` of the program's `ray_tpu.engine.emit`
spans: counted inside the decode program from the lists the kernel was
handed, summed over a window's steps and sparse layers, over the span's
`select_calls` (steps x sparse layers): pages a call. Both are floors (the
queries, the lists and the output are left out, and at 16 operations a byte
the kernel is memory-bound), so the share cannot pass 100."""

import os

from benchmark.manifest import _load_py


def _kernel():
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "sparse_decode_kernel_us.py"),
                    "_bench_metric_sparse_decode_kernel_us")


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count_bytes = getattr(family, "sparse_decode_bytes", None)
    count_flops = getattr(family, "sparse_decode_flops", None)
    if not peaks or count_bytes is None or count_flops is None:
        return None
    kernel = _kernel()
    seconds, counted = kernel.seconds_per_call(obs), kernel.pages(obs)
    if not seconds or not counted:
        return None
    pages = counted[0] / counted[2]
    least = max(
        count_bytes(obs["config"], pages) / peaks["hbm_bytes_per_s"],
        count_flops(obs["config"], pages) / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
