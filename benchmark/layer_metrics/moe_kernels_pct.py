"""Kernels: the share of the device's busy time in the traced slice that goes
to the expert layers' kernels: device time of the custom-calls whose
instruction name holds `moe_` (today `moe_gmm`: the gate-and-up and the down
grouped matmuls of every layer, in prefill and in decode alike; routing, the
sort and the combine are plain XLA and not counted) over the union of the
device's operations. A program without such a kernel gives None."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

# Every Pallas kernel of the expert layers carries this in its name.
FAMILY = "moe_"


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    seconds, calls = _load_py(
        os.path.join(here, "ssm_scan_kernel_ms.py"),
        "_bench_metric_ssm_scan_kernel_ms").totals(obs, FAMILY)
    if calls < program_trace.MIN_EVENTS:
        return None
    busy = sum(t.get("busy_s", 0.0) * t.get("devices", 1)
               for t in obs.get("traces", []))
    return 100.0 * seconds / busy if busy else None
