"""Kernels: mean share of an expert block's HELD experts that some row of a
decode forward chose (an expert nobody chose is never read by `moe_gmm`):
the `experts_touched` of the slice's `ray_tpu.engine.emit` spans (each a
window's sum over its expert blocks and forwards) over held experts x expert
blocks x forwards, the forwards being the `steps` of the slice's
`ray_tpu.engine.dispatch_decode` spans (a window is dispatched before the
one ahead of it is emitted, so the two sums are over windows one apart at
the slice's ends: a hundredth at fifty windows). None where the program
reports no such counter or the family does not say its expert blocks."""

from benchmark import program_trace


def share(obs):
    config, family = obs.get("config") or {}, obs.get("family")
    blocks = getattr(family, "blocks", None)
    held = config.get("n_routed_experts")
    if not callable(blocks) or not held:
        return None
    layers = blocks(config, "E")
    emits = [e["stats"] for e in program_trace.events(
        obs, "ray_tpu.engine.emit") if "experts_touched" in e["stats"]]
    forwards = sum(float(d["stats"]["steps"]) for d in program_trace.events(
        obs, "ray_tpu.engine.dispatch_decode") if "steps" in d["stats"])
    if not layers or not forwards or len(emits) < program_trace.MIN_EVENTS:
        return None
    return sum(float(s["experts_touched"]) for s in emits) / (
        held * layers * forwards)


def read(obs):
    s = share(obs)
    return None if s is None else 100.0 * s
