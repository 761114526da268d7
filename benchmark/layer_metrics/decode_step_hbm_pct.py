"""Jitted decode: the whole token step's share of its roofline. A decode
step at sixteen rows is bound by memory: the bytes it has to move
(`families/<family>.py` `decode_step_bytes`: every weight that multiplies
once, of the routed experts those the slice's rows chose
(`latent_experts_touched_pct`), every slot's recurrent state read and
written) over the chip's peak HBM bandwidth, over a token step's device
time: the decode module's mean launch (`XLA Modules`, as `decode_dev_ms`
reads it) over the mean `steps` of the slice's
`ray_tpu.engine.dispatch_decode` spans (what `decode_window_steps_mean.py`
reads: a window runs `steps` token steps, at most `decode_steps`). The share that bounds a later claim on this cell's
`tpot_p95_ms`; under 100 by construction (the bytes are a floor)."""

import os

from benchmark.manifest import _load_py


def _sibling(name):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, name + ".py"), "_bench_metric_" + name)


def step_seconds(obs):
    """Device seconds of one token step of the decode program, or None."""
    seconds = count = 0.0
    for trace in obs.get("traces", []):
        for name, m in trace.get("modules", {}).items():
            if "decode" in name:
                seconds += m["seconds"]
                count += m["count"]
    steps = _sibling("decode_window_steps_mean").read(obs)
    if not count or not steps:
        return None
    return (seconds / count) / steps


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "decode_step_bytes", None)
    touched = _sibling("latent_experts_touched_pct").share(obs)
    seconds = step_seconds(obs)
    if not peaks or count is None or touched is None or not seconds:
        return None
    config = obs["config"]
    rows = obs["traffic"]["engine_config"]["max_seqs"]
    least = count(config, rows, touched * config["n_routed_experts"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
