"""Kernels: device time of one call of the banded flash forward (one
sliding-window layer of one prefill: every row's queries over the key blocks
inside their window, the call's own keys), from the `XLA Ops` events of
custom-calls whose instruction name holds `swa_flash`. The scan of the device
planes is `swa_decode_kernel_us.py`'s; `swa_flash_mxu_pct` takes its time
from here."""

import os

from benchmark.manifest import _load_py

KERNEL = "swa_flash"


def seconds_per_call(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "swa_decode_kernel_us.py"),
                    "_bench_metric_swa_decode_kernel_us").seconds_per_call(
                        obs, KERNEL)


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e3
