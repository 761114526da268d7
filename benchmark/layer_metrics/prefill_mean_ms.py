"""Jitted prefill: mean time from admission to the first token on the host
(bookkeeping, transfers, the prefill program, its sync), `prefill_ms` of
the engine's `ray_tpu.request.first_token` marks in the traced slice."""

from benchmark import program_trace


def read(obs):
    return program_trace.mean_stat(obs, "ray_tpu.request.first_token",
                                   "prefill_ms")
