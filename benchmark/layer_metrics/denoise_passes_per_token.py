"""Engine scheduler: forwards of the model a row runs per token the engine
emits. A decode dispatch runs `denoise_passes` + `commit_passes` forwards for
each of its `active` rows (its `ray_tpu.engine.dispatch_decode` span; one a
token and no commit pass for a model that yields a token a forward), and
`ray_tpu.engine.emit` counts the `tokens` that left. Block diffusion at 4
denoising steps and a commit pass over blocks of 4 reads 1.25, and more by
what a prompt's remainder, a stop inside a window or a window dispatched
past a request's end throws away."""

from benchmark import program_trace


def read(obs):
    spans = [e["stats"] for e in program_trace.events(
        obs, "ray_tpu.engine.dispatch_decode")
        if "denoise_passes" in e["stats"] and "commit_passes" in e["stats"]
        and "active" in e["stats"]]
    tokens = sum(float(e["stats"].get("tokens", 0))
                 for e in program_trace.events(obs, "ray_tpu.engine.emit"))
    if len(spans) < program_trace.MIN_EVENTS or not tokens:
        return None
    forwards = sum(float(s["active"]) * (float(s["denoise_passes"])
                                         + float(s["commit_passes"]))
                   for s in spans)
    return forwards / tokens
