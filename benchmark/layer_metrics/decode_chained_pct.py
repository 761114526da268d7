"""Engine scheduler: the share of decode windows dispatched off the window
before them while it was still on the device, 100 x the mean `chained` of
the engine's `ray_tpu.engine.dispatch_decode` spans. An unchained dispatch
is one the chip stood still for: the host had read every token before it
built the window's arguments from its mirrors."""

from benchmark import program_trace


def read(obs):
    share = program_trace.mean_stat(obs, "ray_tpu.engine.dispatch_decode",
                                    "chained")
    return None if share is None else 100.0 * share
