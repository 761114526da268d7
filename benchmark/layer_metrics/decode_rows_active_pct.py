"""Engine scheduler: share of the decode program's rows that held a
request, sum of `active` over sum of `max_seqs` of the engine's
`ray_tpu.engine.dispatch_decode` spans (exact, at every dispatch)."""

from benchmark import program_trace


def read(obs):
    spans = [e["stats"] for e in program_trace.events(
        obs, "ray_tpu.engine.dispatch_decode")
        if "active" in e["stats"] and "max_seqs" in e["stats"]]
    rows = sum(float(s["max_seqs"]) for s in spans)
    if len(spans) < program_trace.MIN_EVENTS or not rows:
        return None
    return 100.0 * sum(float(s["active"]) for s in spans) / rows
