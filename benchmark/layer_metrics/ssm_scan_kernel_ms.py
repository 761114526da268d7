"""Kernels: device time of one call of the selective-scan kernel (one Mamba
layer of one prefill: every row's positions walked from a zero state), from
the `XLA Ops` events of custom-calls whose instruction name holds `ssm_scan`.
`program_trace.KERNELS` is a closed list that does not name this kernel, so
the scan of the device planes is made here; the three other `ssm_*` readers
take their totals from this file."""

import os

from benchmark import program_trace, xplane

KERNEL = "ssm_scan"
# Every Pallas kernel of the state-space layers carries this in its name.
FAMILY = "ssm_"


def totals(obs, holds=KERNEL):
    """(device seconds, calls) over the traced slice of the custom-calls
    whose instruction name holds `holds`."""
    seconds, calls = 0.0, 0
    for trace in obs.get("traces", []):
        path = trace.get("path")
        if not path or not os.path.isfile(path):
            continue
        for plane in xplane.load(path).planes:
            if not xplane._is_device(plane.name):
                continue
            line = xplane._line(plane, xplane.OPS_LINE)
            for e in (line.events if line is not None else ()):
                if ("custom-call" in e.name
                        and holds in program_trace.instruction_name(e.name)):
                    seconds += e.duration_ns / 1e9
                    calls += 1
    return seconds, calls


def seconds_per_call(obs):
    """Mean device seconds of a call, or None with fewer than `MIN_EVENTS`
    calls (a program without the kernel)."""
    seconds, calls = totals(obs)
    return seconds / calls if calls >= program_trace.MIN_EVENTS else None


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e3
