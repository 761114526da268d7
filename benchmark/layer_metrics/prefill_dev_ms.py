"""Jitted prefill: device time of the prefill programs per prompt they
prefilled: seconds of the modules whose name holds `prefill` on the trace's
`XLA Modules` line (today `jit_prefill`) over the prompts of the slice's
`ray_tpu.engine.prefill_dispatch` spans (the sum of their `nb`). Both are
counted over the same slice; a launch cut by its edge is in neither or in
one, which a slice of several waves averages out."""

from benchmark import program_trace

SPAN = "ray_tpu.engine.prefill_dispatch"


def read(obs):
    seconds = launches = 0.0
    for trace in obs.get("traces", []):
        for name, m in trace.get("modules", {}).items():
            if "prefill" in name:
                seconds += m["seconds"]
                launches += m["count"]
    spans = [e for e in program_trace.events(obs, SPAN)
             if "nb" in e["stats"]]
    if not launches or not spans:
        return None
    # prompts a launch: the spans' mean, so that a span whose launch the
    # slice's edge cut (or the reverse) does not count for a whole wave
    per_launch = sum(float(e["stats"]["nb"]) for e in spans) / len(spans)
    return seconds / launches / per_launch * 1e3
