"""Kernels: device time of one call of the block-sparse flash forward (one
sparse layer of one row of a prefill: 16,384 queries of 32 heads, each under
its own block mask), from the `XLA Ops` events of custom-calls whose
instruction name holds `sparse_flash`. A slice of four seconds holds a few
prefills of four calls each, so one call is enough to read."""

import os

from benchmark.manifest import _load_py

KERNEL = "sparse_flash"


def seconds_per_call(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "sparse_decode_kernel_us.py"),
                    "_bench_metric_sparse_decode_kernel_us"
                    ).seconds_per_call(obs, KERNEL, least=1)


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e3
