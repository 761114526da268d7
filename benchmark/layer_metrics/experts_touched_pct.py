"""Kernels: mean share of a layer's experts that some token of a forward
chose (an expert nobody chose is never read by `moe_gmm`). The decode
program counts, per window, the experts touched summed over its layers and
forwards; the engine reports the sum as `experts_touched` on its
`ray_tpu.engine.emit` span, and the forwards of a window as `denoise_passes`
and `commit_passes` on `ray_tpu.engine.dispatch_decode`."""

from benchmark import program_trace


def share(obs):
    """Touched experts over experts held, both over layers, forwards and
    windows; None where the program reports no such counter."""
    config = obs.get("config") or {}
    held = config.get("num_experts")
    layers = config.get("num_hidden_layers")
    emits = [e["stats"] for e in program_trace.events(
        obs, "ray_tpu.engine.emit") if "experts_touched" in e["stats"]]
    passes = [float(d["stats"]["denoise_passes"])
              + float(d["stats"]["commit_passes"])
              for d in program_trace.events(
                  obs, "ray_tpu.engine.dispatch_decode")
              if "denoise_passes" in d["stats"]
              and "commit_passes" in d["stats"]]
    if (not held or not layers or not passes
            or len(emits) < program_trace.MIN_EVENTS):
        return None
    # every window runs the same forwards
    per_window = held * layers * passes[0]
    return sum(float(s["experts_touched"]) for s in emits) / (
        len(emits) * per_window)


def read(obs):
    s = share(obs)
    return None if s is None else 100.0 * s
