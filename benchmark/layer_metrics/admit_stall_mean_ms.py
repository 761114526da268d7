"""Engine scheduler: mean length of `ray_tpu.engine.admit`, from the first
page bookkeeping to the last first token on the host. No decode window is
dispatched inside it, so every running stream stalls that long. Listed for
`chat-steady` alone: `decode-heavy`'s traced slice holds some three
admissions, under the floor of five events."""

from benchmark import program_trace


def read(obs):
    spans = program_trace.events(obs, "ray_tpu.engine.admit")
    if len(spans) < program_trace.MIN_EVENTS:
        return None
    return sum(e["duration_ns"] for e in spans) / len(spans) / 1e6
