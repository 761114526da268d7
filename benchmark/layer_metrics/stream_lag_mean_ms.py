"""Runtime, the stream's producer half: mean time from the engine loop
handing over the step that finished a request to the replica's handler
having given the runtime the stream's last item, `lag_ms` of the program's
`ray_tpu.request.stream_done` marks in the traced slice."""

from benchmark import program_trace


def read(obs):
    return program_trace.mean_stat(obs, "ray_tpu.request.stream_done",
                                   "lag_ms")
