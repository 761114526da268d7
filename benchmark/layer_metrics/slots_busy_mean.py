"""Engine scheduler: `stats()["running"]` sampled at 10 Hz in the window."""


def read(obs):
    inside = [s["running"] for s in obs.get("stats_samples", [])
              if 0.0 <= s["t"] <= obs["seconds"]]
    return sum(inside) / len(inside) if inside else None
