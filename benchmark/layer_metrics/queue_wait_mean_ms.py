"""Engine scheduler: mean time from a request's making to its admission
into a slot, `queue_ms` of the engine's `ray_tpu.request.first_token` marks
in the traced slice."""

from benchmark import program_trace


def read(obs):
    return program_trace.mean_stat(obs, "ray_tpu.request.first_token",
                                   "queue_ms")
