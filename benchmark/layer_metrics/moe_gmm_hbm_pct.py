"""Kernels: the grouped matmul's share of its roofline. At decode the kernel
is bound by memory: the bytes a layer's two `moe_gmm` calls have to move in
one forward (`families/<family>.py` `moe_gmm_bytes`: the weights of the
experts some token chose, once, and the activations of every row of the
engine, `max_seqs` x `block_length` tokens, because an idle row is computed
too) over the chip's peak HBM bandwidth, over the calls' device time."""

import os

from benchmark.manifest import _load_py


def _sibling(name):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, name + ".py"), "_bench_metric_" + name)


def read(obs):
    peaks, family = obs.get("peaks"), obs.get("family")
    count = getattr(family, "moe_gmm_bytes", None)
    if not peaks or count is None:
        return None
    seconds = _sibling("moe_gmm_kernel_us").seconds_per_layer(obs)
    touched = _sibling("experts_touched_pct").share(obs)
    if not seconds or touched is None:
        return None
    config = obs["config"]
    tokens = (obs["traffic"]["engine_config"]["max_seqs"]
              * config.get("block_length", 1))
    least = count(config, tokens, touched * config["num_experts"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
