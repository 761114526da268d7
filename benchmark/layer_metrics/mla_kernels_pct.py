"""Kernels: the share of the device's busy time in the traced slice that goes
to latent attention's kernels: device time of the custom-calls whose
instruction name holds `mla_` (`mla_decode`, a call a layer and token step,
and `mla_flash`, a call a layer and row of a prefill; the projections around
them, the absorbed ones among them, are plain XLA and not counted) over the
union of the device's operations. A program without such a kernel gives
None."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

# Every Pallas kernel of latent attention carries this in its name.
FAMILY = "mla_"


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    seconds, calls = _load_py(
        os.path.join(here, "mla_decode_kernel_us.py"),
        "_bench_metric_mla_decode_kernel_us").custom_calls(obs, FAMILY)
    if calls < program_trace.MIN_EVENTS:
        return None
    busy = sum(t.get("busy_s", 0.0) * t.get("devices", 1)
               for t in obs.get("traces", []))
    return 100.0 * seconds / busy if busy else None
