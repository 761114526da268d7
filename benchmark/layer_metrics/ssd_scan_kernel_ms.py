"""Kernels: device time of one call of the Mamba-2 scan kernel (one Mamba-2
layer of one prefill: every row's positions walked chunk by chunk from a zero
state), from the `XLA Ops` events of custom-calls whose instruction name holds
`ssd_scan`. The scan of the device planes is `ssm_scan_kernel_ms.py`'s, asked
for this kernel's name; `ssd_scan_roofline_pct` takes its time from here."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

KERNEL = "ssd_scan"


def custom_calls(obs, holds):
    """(device seconds, calls) over the traced slice of the custom-calls
    whose instruction name holds `holds`."""
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "ssm_scan_kernel_ms.py"),
                    "_bench_metric_ssm_scan_kernel_ms").totals(obs, holds)


def seconds_per_call(obs):
    """Mean device seconds of a call, or None with fewer than `MIN_EVENTS`
    calls (a program without the kernel)."""
    seconds, calls = custom_calls(obs, KERNEL)
    return seconds / calls if calls >= program_trace.MIN_EVENTS else None


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e3
