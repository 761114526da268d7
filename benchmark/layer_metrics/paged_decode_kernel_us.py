"""Kernels: device time of one call of the paged decode kernel (one layer
of one token step), from the `XLA Ops` events of custom-calls named
`paged_decode`."""

from benchmark import program_trace


def read(obs):
    s = program_trace.kernel_seconds_per_call(obs, ["paged_decode"])
    return None if s is None else s * 1e6
