"""Kernels: the share of the device's busy time in the traced slice that goes
to sparse attention's kernels: device time of the custom-calls whose
instruction name holds `sparse_` (`sparse_decode`, a call a sparse layer and
token step, and `sparse_flash`, a call a sparse layer and row of a prefill;
the selection around them is plain XLA and not counted) over the union of
the device's operations. A program without such a kernel gives None."""

import os

from benchmark.manifest import _load_py

# Every Pallas kernel of sparse attention carries this in its name.
FAMILY = "sparse_"


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    seconds, calls = _load_py(
        os.path.join(here, "sparse_decode_kernel_us.py"),
        "_bench_metric_sparse_decode_kernel_us").custom_calls(obs, FAMILY)
    if not calls:
        return None
    busy = sum(t.get("busy_s", 0.0) * t.get("devices", 1)
               for t in obs.get("traces", []))
    return 100.0 * seconds / busy if busy else None
