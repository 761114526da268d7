"""Runtime, the stream's producer half: mean number of tokens a streamed
request made per item its handler gave the runtime (header and closing
frames counted as items), `tokens_per_item` of the program's
`ray_tpu.request.stream_done` marks in the traced slice."""

from benchmark import program_trace


def read(obs):
    return program_trace.mean_stat(obs, "ray_tpu.request.stream_done",
                                   "tokens_per_item")
