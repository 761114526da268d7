"""Kernels: device time of one layer's flash attention backward, the
`flash_bwd_dq` and `flash_bwd_dkv` kernels together, per `flash_bwd_dq`
call."""

from benchmark import program_trace


def read(obs):
    s = program_trace.kernel_seconds_per_call(
        obs, ["flash_bwd_dq", "flash_bwd_dkv"])
    return None if s is None else s * 1e3
