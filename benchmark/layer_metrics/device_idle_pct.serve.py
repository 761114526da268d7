"""Device: 1 - union of device-op intervals over the traced slice of the
window, in the replica (mean over replicas)."""


def read(obs):
    traces = [t for t in obs.get("traces", []) if t.get("window_s")]
    if not traces:
        return None
    return 100.0 * (1.0 - sum(t["busy_s"] / t["window_s"] for t in traces)
                    / len(traces))
