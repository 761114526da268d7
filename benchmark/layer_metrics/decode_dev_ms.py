"""Jitted decode: device time of the decode program per token step, from
the trace's `XLA Modules` line (today's module is named after the Python
function, `jit_decode`); one launch makes `decode_steps` tokens per slot."""


def read(obs):
    seconds = count = 0.0
    for trace in obs.get("traces", []):
        for name, m in trace.get("modules", {}).items():
            if "decode" in name:
                seconds += m["seconds"]
                count += m["count"]
    if not count:
        return None
    steps = obs["replicas"][0].get("decode_steps", 1)
    return seconds / count / steps * 1e3
