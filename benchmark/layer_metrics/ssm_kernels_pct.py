"""Kernels: the share of the device's busy time in the traced slice that goes
to the state-space layers' kernels: device time of the custom-calls whose
instruction name holds `ssm_` (today `ssm_scan`, the prefill's; the one-token
step is plain XLA and not counted) over the union of the device's operations.
A program without such a kernel gives None."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py


def _ssm(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "ssm_scan_kernel_ms.py"),
                    "_bench_metric_ssm_scan_kernel_ms")


def read(obs):
    ssm = _ssm(obs)
    seconds, calls = ssm.totals(obs, ssm.FAMILY)
    if calls < program_trace.MIN_EVENTS:
        return None
    busy = sum(t.get("busy_s", 0.0) * t.get("devices", 1)
               for t in obs.get("traces", []))
    return 100.0 * seconds / busy if busy else None
