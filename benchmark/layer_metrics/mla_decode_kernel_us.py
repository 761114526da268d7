"""Kernels: device time of one call of the latent decode kernel (one layer of
one token step: every row's walk over the pages of its latent cache, each
page read once as keys and as values), from the `XLA Ops` events of
custom-calls whose instruction name holds `mla_decode`. The scan of the
device planes is `ssm_scan_kernel_ms.py`'s, asked for this kernel's name; the
other `mla_*` readers take their times from here."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

KERNEL = "mla_decode"


def custom_calls(obs, holds):
    """(device seconds, calls) over the traced slice of the custom-calls
    whose instruction name holds `holds`."""
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "ssm_scan_kernel_ms.py"),
                    "_bench_metric_ssm_scan_kernel_ms").totals(obs, holds)


def seconds_per_call(obs, kernel=KERNEL):
    """Mean device seconds of a call of `kernel`, or None with fewer than
    `MIN_EVENTS` calls (a program without the kernel)."""
    seconds, calls = custom_calls(obs, kernel)
    return seconds / calls if calls >= program_trace.MIN_EVENTS else None


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e6
