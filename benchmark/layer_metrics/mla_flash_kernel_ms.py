"""Kernels: device time of one call of latent attention's flash forward (one
layer of one row of a prefill: the row's queries over its own keys of 192 and
values of 128, causal), from the `XLA Ops` events of custom-calls whose
instruction name holds `mla_flash`. The scan of the device planes is
`mla_decode_kernel_us.py`'s; `mla_flash_mxu_pct` takes its time from here."""

import os

from benchmark.manifest import _load_py

KERNEL = "mla_flash"


def seconds_per_call(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "mla_decode_kernel_us.py"),
                    "_bench_metric_mla_decode_kernel_us").seconds_per_call(
                        obs, KERNEL)


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e3
