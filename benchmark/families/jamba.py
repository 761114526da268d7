"""Jamba (a Mamba-1 selective state space on most layers, multi-query
attention on every `attn_layer_period`-th, a dense SwiGLU MLP after each):
the config file's published keys -> `JambaConfig` arguments, the program's
model for them, the parameters that multiply, and the bytes its prefill scan
moves (for a roofline share). `references/jamba.py` holds the family's plain
reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.jamba"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the jamba family")
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("models/jamba.py has a tied head only")
    if cfg.get("num_experts", 1) != 1 or cfg.get("num_experts_per_tok",
                                                  1) != 1:
        raise ValueError("models/jamba.py has the dense MLP only")
    if cfg.get("mamba_proj_bias") or not cfg.get("mamba_conv_bias"):
        raise ValueError("models/jamba.py: a convolution bias, and no bias "
                         "on the mixer's projections")
    if cfg.get("sliding_window"):
        raise ValueError("models/jamba.py has no sliding window")
    if cfg.get("num_logits_to_keep") != 1:
        raise ValueError("models/jamba.py runs a prefill's head on one "
                         "position a row")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "num_layers": cfg["num_hidden_layers"],
        "attn_layer_period": cfg["attn_layer_period"],
        "attn_layer_offset": cfg["attn_layer_offset"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": _head_dim(cfg),
        "mamba_d_state": cfg["mamba_d_state"],
        "mamba_d_conv": cfg["mamba_d_conv"],
        "mamba_expand": cfg["mamba_expand"],
        "mamba_dt_rank": cfg["mamba_dt_rank"],
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.jamba import JambaConfig, JambaModel

    return JambaModel(JambaConfig(**kw))


def _head_dim(cfg: Dict[str, Any]) -> int:
    """The family's convention where the config is silent."""
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def _d_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def attention_layers(cfg: Dict[str, Any]) -> int:
    return sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
               for i in range(cfg["num_hidden_layers"]))


def mamba_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - attention_layers(cfg)


def mixer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one Mamba mixer, by tensor."""
    e, d = cfg["hidden_size"], _d_inner(cfg)
    n, rank, width = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
                      cfg["mamba_d_conv"])
    return {"in_proj": e * 2 * d, "conv1d": d * width + d,
            "x_proj": d * (rank + 2 * n), "dt_proj": rank * d + d,
            "A_log": d * n, "D": d, "out_proj": d * e,
            "norms": rank + 2 * n}


def _attention_proj(cfg: Dict[str, Any]) -> int:
    """q and o, k and v of one attention layer."""
    e, d = cfg["hidden_size"], _head_dim(cfg)
    return 2 * e * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])


def parameters(cfg: Dict[str, Any]) -> int:
    """Every parameter of the model; the head is the embedding."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    rest = 3 * e * f + 2 * e     # the MLP and the block's two norms
    return (mamba_layers(cfg) * (sum(mixer_params(cfg).values()) + rest)
            + attention_layers(cfg) * (_attention_proj(cfg) + rest)
            + e * cfg["vocab_size"] + e)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections and the head (the embedding, used twice: as a
    gather, which does not count, and as the head, which does). The
    convolution, the recurrence and the norms are elementwise."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    m = mixer_params(cfg)
    mixer = (m["in_proj"] + m["x_proj"] + cfg["mamba_dt_rank"] * _d_inner(cfg)
             + m["out_proj"])
    ffn = 3 * e * f
    return (mamba_layers(cfg) * (mixer + ffn)
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
    """One Mamba layer's float32 state [d_state, d_inner] of `rows` slots."""
    return rows * cfg["mamba_d_state"] * _d_inner(cfg) * 4


def ssm_scan_bytes(cfg: Dict[str, Any], positions: int) -> int:
    """Bytes one layer's `ssm_scan` has to move for `positions` positions
    (over all the rows of a call): a position's x, z (read) and out
    (written) in bf16, dt in float32, over the d_inner channels, and its B
    and C, d_state float32 each. The state [d_state, d_inner] stays in VMEM
    and is written once a row, A and D are read once a channel block: both
    are left out, so this is a floor."""
    d, n = _d_inner(cfg), cfg["mamba_d_state"]
    return positions * (d * (2 + 2 + 2 + 4) + 2 * n * 4)
