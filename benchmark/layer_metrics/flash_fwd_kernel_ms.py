"""Kernels: device time of one call of the flash attention forward kernel
(one layer; under remat the backward pass calls it again), from the `XLA
Ops` events of custom-calls named `flash_fwd`."""

from benchmark import program_trace


def read(obs):
    s = program_trace.kernel_seconds_per_call(obs, ["flash_fwd"])
    return None if s is None else s * 1e3
