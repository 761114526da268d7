"""Kernels: the latent grouped matmul's share of its roofline. At a decode
step's one or two rows an expert the kernel is bound by memory: the bytes an
expert block's two `moe_gmm` calls have to move in one forward
(`families/<family>.py` `moe_gmm_bytes`: the up and down weights of the held
experts some row chose, at the slice's own `latent_experts_touched_pct`,
once, and the rows' latents and activations; every one of the engine's
`max_seqs` rows, because an idle row is routed too) over the chip's peak HBM
bandwidth, over the two calls' device time."""

import os

from benchmark.manifest import _load_py


def _sibling(name):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, name + ".py"), "_bench_metric_" + name)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    seconds = _sibling("moe_gmm_kernel_us").seconds_per_layer(obs)
    touched = _sibling("latent_experts_touched_pct").share(obs)
    if not peaks or not seconds or touched is None:
        return None
    config = obs["config"]
    rows = obs["traffic"]["engine_config"]["max_seqs"]
    least = family.moe_gmm_bytes(
        config, rows, touched * config["n_routed_experts"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
