"""SDAR-MoE (the Qwen3-MoE block, every feed-forward 128 experts routed
top-8, generated from by diffusion over blocks): the config file's published
keys -> `SdarMoeConfig` arguments, the program's model for them, the
parameters that multiply, the bytes its grouped matmul moves (for a roofline
share) and the forwards a token costs. `references/sdar_moe.py` holds the
family's plain reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.sdar_moe"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the sdar_moe family")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/sdar_moe.py has an untied head only")
    if cfg.get("attention_bias"):
        raise ValueError("models/sdar_moe.py has no attention bias")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step") != 1:
        raise ValueError("models/sdar_moe.py has no dense layer")
    if not cfg.get("norm_topk_prob"):
        raise ValueError("models/sdar_moe.py renormalises the top-k weights")
    if cfg.get("rope_scaling") or cfg.get("use_sliding_window"):
        raise ValueError("models/sdar_moe.py has plain rotary positions "
                         "and no sliding window")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "num_experts": cfg["num_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "num_layers": cfg["num_hidden_layers"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "block_length": cfg["block_length"],
        "denoising_steps": cfg["denoising_steps"],
        "remasking": cfg["remasking"],
        "mask_token_id": cfg["mask_token_id"],
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeModel

    return SdarMoeModel(SdarMoeConfig(**kw))


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    attention projections, the router, the `num_experts_per_tok` experts a
    token goes through (not the 128 a layer holds) and the output head."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    attn = e * q + 2 * e * kv + q * e
    experts = cfg["num_experts_per_tok"] * 3 * e * cfg["moe_intermediate_size"]
    layer = attn + e * cfg["num_experts"] + experts
    return cfg["num_hidden_layers"] * layer + e * cfg["vocab_size"]


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of softmax attention's two matmuls per token at
    sequence length `seq` (the block mask is causal but for a block)."""
    per_layer = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    return cfg["num_hidden_layers"] * per_layer * (0.5 if causal else 1.0)


def moe_gmm_bytes(cfg: Dict[str, Any], tokens: int,
                  experts_touched: float, itemsize: int = 2) -> float:
    """Bytes the two `moe_gmm` calls of one layer and one forward have to
    move: the gate, up and down weights of the experts some token chose,
    once each, and per assignment (token x `num_experts_per_tok`) its
    activations into and out of both calls. It counts no padding row and no
    weight read twice, so the kernel cannot do with less."""
    e, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = tokens * cfg["num_experts_per_tok"]
    weights = experts_touched * 3 * e * i
    activations = rows * (e + 2 * i) + rows * (i + e)
    return float(itemsize) * (weights + activations)


def forward_passes_per_token(cfg: Dict[str, Any]) -> float:
    """Forwards over a block per token it yields: the denoising passes and
    the commit pass over `block_length` tokens."""
    return (cfg["denoising_steps"] + 1) / cfg["block_length"]
