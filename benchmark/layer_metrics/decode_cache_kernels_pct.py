"""Kernels: the share of the decode program's device time that goes to the
two kernels that read the cache: `gdn_decode` (the recurrent state of a
linear layer) and `paged_decode` (the K/V pages of a full layer). Device
time of their custom-calls over the decode program's on the `XLA Modules`
line, both from the traced slice. A program without `gdn_decode` gives
None."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py


def _gdn_totals(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "gdn_decode_kernel_us.py"),
                    "_bench_metric_gdn_decode_kernel_us").totals(obs)


def read(obs):
    gdn, calls = _gdn_totals(obs)
    if calls < program_trace.MIN_EVENTS:
        return None
    paged = sum(r["kernels"].get("paged_decode", {}).get("seconds", 0.0)
                for r in program_trace.of(obs))
    program = sum(m["seconds"] for trace in obs.get("traces", [])
                  for name, m in trace.get("modules", {}).items()
                  if "decode" in name)
    return 100.0 * (gdn + paged) / program if program else None
