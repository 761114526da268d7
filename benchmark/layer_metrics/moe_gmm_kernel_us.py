"""Kernels: device time of the grouped matmul of one mixture-of-experts layer
in one forward of the decode program, its two `moe_gmm` calls together (gate
and up, then down), from the `XLA Ops` events of custom-calls whose
instruction name holds `moe_gmm` and that start inside a launch of a module
whose name holds `decode` (a prefill runs the kernel over many more rows).
`program_trace.KERNELS` is a closed list that does not name this kernel, so
the scan of the device planes is made here."""

import bisect
import os

from benchmark import program_trace, xplane

KERNEL = "moe_gmm"
CALLS_PER_LAYER = 2


def totals(obs):
    """(device seconds, calls) of `moe_gmm` under the decode program over
    the traced slice."""
    seconds, calls = 0.0, 0
    for trace in obs.get("traces", []):
        path = trace.get("path")
        if not path or not os.path.isfile(path):
            continue
        for plane in xplane.load(path).planes:
            if not xplane._is_device(plane.name):
                continue
            ops = xplane._line(plane, xplane.OPS_LINE)
            mods = xplane._line(plane, xplane.MODULES_LINE)
            if ops is None or mods is None:
                continue
            launches = sorted(
                (m.start_ns, m.start_ns + m.duration_ns) for m in mods.events
                if "decode" in xplane.module_name(m.name))
            starts = [a for a, _ in launches]
            for e in ops.events:
                if ("custom-call" not in e.name
                        or KERNEL not in program_trace.instruction_name(
                            e.name)):
                    continue
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns < launches[i][1]:
                    seconds += e.duration_ns / 1e9
                    calls += 1
    return seconds, calls


def seconds_per_layer(obs):
    """Mean device seconds of a layer's two calls, or None with fewer than
    `MIN_EVENTS` calls (a program without the kernel)."""
    seconds, calls = totals(obs)
    if calls < program_trace.MIN_EVENTS:
        return None
    return seconds / (calls / CALLS_PER_LAYER)


def read(obs):
    s = seconds_per_layer(obs)
    return None if s is None else s * 1e6
