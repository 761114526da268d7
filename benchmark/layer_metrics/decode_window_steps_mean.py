"""Engine scheduler: mean token steps of a decode window, `steps` of the
engine's `ray_tpu.engine.dispatch_decode` spans: `decode_steps` where no
window was shortened for a request that could be admitted, half of it where
every one was. A `jit_decode` launch's device time over this is the time
of one token step."""

from benchmark import program_trace


def read(obs):
    return program_trace.mean_stat(obs, "ray_tpu.engine.dispatch_decode",
                                   "steps")
