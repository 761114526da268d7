"""Engine scheduler: mean number of requests one prefill program takes,
`nb` of the engine's `ray_tpu.engine.prefill_dispatch` spans."""

from benchmark import program_trace


def read(obs):
    return program_trace.mean_stat(obs, "ray_tpu.engine.prefill_dispatch",
                                   "nb")
