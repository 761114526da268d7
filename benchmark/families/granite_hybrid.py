"""Granite 4.0-H (a Mamba-2 mixer on most layers, grouped-query attention
without positions on the rest, a top-k mixture of experts beside a shared
expert after each): the config file's published keys -> `GraniteHybridConfig`
arguments, the program's model for them, the parameters it holds and those
that multiply, and what its prefill scan has to move and multiply (for a
roofline share). `references/granite_hybrid.py` holds the family's plain
reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.granite_hybrid"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the granite_hybrid family")
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("models/granite_hybrid.py has a tied head only")
    if cfg.get("position_embedding_type") != "nope" or cfg.get(
            "rope_scaling"):
        raise ValueError("models/granite_hybrid.py has attention without "
                         "positional encoding only")
    if cfg.get("attention_bias") or cfg.get("mamba_proj_bias") or not cfg.get(
            "mamba_conv_bias"):
        raise ValueError("models/granite_hybrid.py: a convolution bias, and "
                         "no bias on any projection")
    if cfg.get("mamba_n_groups") != 1:
        raise ValueError("models/granite_hybrid.py: B and C of one group")
    if cfg["mamba_expand"] * cfg["hidden_size"] != _d_inner(cfg):
        raise ValueError("mamba_n_heads * mamba_d_head is not mamba_expand "
                         "* hidden_size")
    if cfg.get("normalization_function") != "rmsnorm" or cfg.get(
            "hidden_act") != "silu":
        raise ValueError("models/granite_hybrid.py: RMSNorm and SiLU")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "shared_intermediate_size": cfg["shared_intermediate_size"],
        # The router's columns are the published experts; this chip holds
        # the first `num_local_experts` (the file's, cut by `reduced`).
        "num_experts": _routed(cfg),
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "experts_held": [0, cfg["num_local_experts"]],
        "layer_types": layer_types(cfg),
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": _head_dim(cfg),
        "mamba_n_heads": cfg["mamba_n_heads"],
        "mamba_d_head": cfg["mamba_d_head"],
        "mamba_d_state": cfg["mamba_d_state"],
        "mamba_d_conv": cfg["mamba_d_conv"],
        "mamba_chunk_size": cfg["mamba_chunk_size"],
        "embedding_multiplier": float(cfg["embedding_multiplier"]),
        "attention_multiplier": float(cfg["attention_multiplier"]),
        "residual_multiplier": float(cfg["residual_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                               GraniteHybridModel)

    return GraniteHybridModel(GraniteHybridConfig(**kw))


def layer_types(cfg: Dict[str, Any]):
    """The kinds of the layers that are run: the first `num_hidden_layers`
    of the published order."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _routed(cfg: Dict[str, Any]) -> int:
    """Experts the router chooses among: the published count."""
    return cfg.get("published", {}).get("num_local_experts",
                                        cfg["num_local_experts"])


def _head_dim(cfg: Dict[str, Any]) -> int:
    """The family's convention where the config is silent."""
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def _d_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def _conv_dim(cfg: Dict[str, Any]) -> int:
    return _d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def attention_layers(cfg: Dict[str, Any]) -> int:
    return layer_types(cfg).count("attention")


def mamba_layers(cfg: Dict[str, Any]) -> int:
    return layer_types(cfg).count("mamba")


def mixer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one Mamba-2 mixer, by tensor."""
    e, d, heads = cfg["hidden_size"], _d_inner(cfg), cfg["mamba_n_heads"]
    conv = _conv_dim(cfg)
    return {"in_proj": e * (d + conv + heads),
            "conv1d": conv * cfg["mamba_d_conv"] + conv,
            "A_log": heads, "D": heads, "dt_bias": heads, "norm": d,
            "out_proj": d * e}


def _attention_proj(cfg: Dict[str, Any]) -> int:
    """q and o, k and v of one attention layer."""
    e, d = cfg["hidden_size"], _head_dim(cfg)
    return 2 * e * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: gate and up, then down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def parameters(cfg: Dict[str, Any]) -> int:
    """Every parameter this configuration holds: `num_local_experts` experts
    a layer (the file's), the router over all that are routed; the head is
    the embedding."""
    e = cfg["hidden_size"]
    rest = (e * _routed(cfg) + cfg["num_local_experts"] * expert_params(cfg)
            + shared_params(cfg) + 2 * e)   # and the block's two norms
    return (mamba_layers(cfg) * (sum(mixer_params(cfg).values()) + rest)
            + attention_layers(cfg) * (_attention_proj(cfg) + rest)
            + e * cfg["vocab_size"] + e)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token ON
    THIS CHIP: the mixers' projections, the router, the shared expert, the
    share of a token's `num_experts_per_tok` experts that is held here
    (`num_local_experts` of those routed, with a router that favours none)
    and the head (the embedding, used twice: as a gather, which does not
    count, and as the head, which does). The convolution, the recurrence and
    the norms are counted elsewhere (`ssd_scan_flops`) or elementwise."""
    e = cfg["hidden_size"]
    m = mixer_params(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_local_experts"] / _routed(cfg)
    ffn = e * _routed(cfg) + held * expert_params(cfg) + shared_params(cfg)
    return int(mamba_layers(cfg) * (m["in_proj"] + m["out_proj"] + ffn)
               + attention_layers(cfg) * (_attention_proj(cfg) + ffn)
               + e * cfg["vocab_size"])


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of softmax attention's two matmuls per token at
    sequence length `seq`: the attention layers only (a Mamba layer's cost
    does not depend on `seq`)."""
    full = 2 * 2 * cfg["num_attention_heads"] * _head_dim(cfg) * seq
    return attention_layers(cfg) * full * (0.5 if causal else 1.0)


def state_bytes(cfg: Dict[str, Any], rows: int) -> int:
    """One Mamba-2 layer's float32 state [heads, d_head, d_state] of `rows`
    slots."""
    return rows * _d_inner(cfg) * cfg["mamba_d_state"] * 4


def ssd_scan_bytes(cfg: Dict[str, Any], positions: int) -> int:
    """Bytes one layer's `ssd_scan` has to move for `positions` positions
    (over all the rows of a call): a position's x (read) and y (written) in
    bf16 over the d_inner channels, its B and C (d_state bf16 each), and per
    head its dt and the running sum of dt A in float32. The state is
    written once a row and D read once a block: both are left out, as is the
    second layout of the running sum the kernel is handed, so this is a
    floor."""
    d, n, heads = _d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_n_heads"]
    return positions * (d * (2 + 2) + 2 * n * 2 + heads * (4 + 4))


def ssd_scan_flops(cfg: Dict[str, Any], positions: int) -> int:
    """Multiply-adds times two that one layer's `ssd_scan` cannot do without
    for `positions` positions in chunks of `mamba_chunk_size`: per position
    C B^T against its chunk's positions up to itself (half a chunk on
    average, once for all heads), and per head the same half chunk of
    (C B^T o L) (dt x), C S^T for the state carried in, and (w dt x)^T B for
    the state handed on. The kernel multiplies whole chunk squares (the
    upper triangle by zeros), which is not counted: a floor."""
    d, n, q = _d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_chunk_size"]
    half = (q + 1) / 2
    return int(2 * positions * (half * n + half * d + 2 * n * d))
