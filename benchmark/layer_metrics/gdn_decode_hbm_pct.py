"""Kernels: the gated-delta decode kernel's share of its roofline. The
kernel is bound by memory: the bytes one call has to move
(`families/<family>.py` `gdn_decode_bytes`, every one of the engine's
`max_seqs` rows, because the kernel passes an inactive row's state through
too) over the chip's peak HBM bandwidth, over the call's device time."""

import os

from benchmark.manifest import _load_py


def _kernel_seconds(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "gdn_decode_kernel_us.py"),
                    "_bench_metric_gdn_decode_kernel_us").seconds_per_call(obs)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "gdn_decode_bytes", None)
    if not peaks or count is None:
        return None
    seconds = _kernel_seconds(obs)
    if not seconds:
        return None
    rows = obs["traffic"]["engine_config"]["max_seqs"]
    least = count(obs["config"], rows) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
