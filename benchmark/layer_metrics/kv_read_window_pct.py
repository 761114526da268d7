"""Kernels: the K/V tokens a token step reads over all its attention layers as
a share of what it would read were every layer a full one: (sliding layers x
`window_tokens` + full layers x `context_tokens`) over (all layers x
`context_tokens`), both of the program's `ray_tpu.engine.dispatch_decode`
spans (sums over the active rows of min(length, window) and of the length,
from the host's mirrors at the dispatch), summed over the slice with each span
weighted by its token `steps`; the counts of layers are the family's
(`sliding_layers`, `full_layers`). 100 while every context is inside one
window; at contexts of 2.3k-4.6k and six sliding layers of eight, about a half.
None where the program's spans carry no such counter."""

from benchmark import program_trace

SPAN = "ray_tpu.engine.dispatch_decode"


def read(obs):
    family = obs.get("family")
    if not hasattr(family, "sliding_layers"):
        return None
    sliding = family.sliding_layers(obs["config"])
    full = family.full_layers(obs["config"])
    stats = [e["stats"] for e in program_trace.events(obs, SPAN)
             if "window_tokens" in e["stats"]
             and "context_tokens" in e["stats"] and "steps" in e["stats"]]
    weigh = lambda key: sum(float(s[key]) * float(s["steps"]) for s in stats)
    context = weigh("context_tokens")
    if len(stats) < program_trace.MIN_EVENTS or not context:
        return None
    return 100.0 * (sliding * weigh("window_tokens") + full * context) / (
        (sliding + full) * context)
