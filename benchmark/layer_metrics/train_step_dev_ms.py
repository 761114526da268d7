"""Jitted train step: device time of one launch of the step program, from
the trace's `XLA Modules` line (`jit_step` today)."""


def read(obs):
    seconds = count = 0.0
    for trace in obs.get("traces", []):
        for name, m in trace.get("modules", {}).items():
            if "step" in name:
                seconds += m["seconds"]
                count += m["count"]
    return seconds / count * 1e3 if count else None
