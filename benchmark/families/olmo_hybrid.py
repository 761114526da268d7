"""Olmo-Hybrid (gated-delta linear attention on three layers of four, full
attention on the fourth): the config file's published keys ->
`OlmoHybridConfig` arguments, the program's model for them, the parameters
that multiply, and the bytes its decode kernels move (for roofline shares).
`references/olmo_hybrid.py` holds the family's plain reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.olmo_hybrid"
LINEAR, FULL = "linear_attention", "full_attention"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the olmo_hybrid family")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/olmo_hybrid.py has an untied head only")
    if cfg.get("attention_bias"):
        raise ValueError("models/olmo_hybrid.py has no attention bias")
    if cfg["rope_parameters"].get("rope_theta") is not None:
        raise ValueError("models/olmo_hybrid.py has no rotary embedding")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "layer_types": list(cfg["layer_types"]),
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": _head_dim(cfg),
        "linear_num_key_heads": cfg["linear_num_key_heads"],
        "linear_num_value_heads": cfg["linear_num_value_heads"],
        "linear_key_head_dim": cfg["linear_key_head_dim"],
        "linear_value_head_dim": cfg["linear_value_head_dim"],
        "linear_conv_kernel_dim": cfg["linear_conv_kernel_dim"],
        "linear_allow_neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig, OlmoHybridModel

    return OlmoHybridModel(OlmoHybridConfig(**kw))


def _head_dim(cfg: Dict[str, Any]) -> int:
    """Of a full layer; the family's convention where the config is silent."""
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def _layers(cfg: Dict[str, Any], kind: str) -> int:
    return sum(t == kind for t in cfg["layer_types"])


def _linear_dims(cfg: Dict[str, Any]):
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections and the output head. The embedding is a gather, the
    convolutions and norms are elementwise."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, dk, dv = _linear_dims(cfg)
    d = _head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    ffn = 3 * e * f
    # q, k; v and the output gate; out; the two per-head gates a, b
    linear = 2 * e * h * dk + 2 * e * h * dv + h * dv * e + 2 * e * h
    full = e * q + 2 * e * kv + q * e
    return (_layers(cfg, LINEAR) * (linear + ffn)
            + _layers(cfg, FULL) * (full + ffn) + e * cfg["vocab_size"])


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of softmax attention's two matmuls per token at
    sequence length `seq`: the full layers only (a linear layer's cost does
    not depend on `seq`)."""
    d = _head_dim(cfg)
    full = 2 * 2 * cfg["num_attention_heads"] * d * seq
    return _layers(cfg, FULL) * full * (0.5 if causal else 1.0)


def state_bytes(cfg: Dict[str, Any], rows: int) -> int:
    """One linear layer's float32 state [heads, key_dim, value_dim] of
    `rows` slots."""
    h, dk, dv = _linear_dims(cfg)
    return rows * h * dk * dv * 4


def gdn_decode_bytes(cfg: Dict[str, Any], rows: int) -> int:
    """Bytes one `gdn_decode` call (one layer, one token a row) moves: each
    row's state read once and written once, plus per head, in float32 as
    the kernel takes them, four key-sized columns (k, alpha*beta*k, q,
    alpha), beta*v in and o out."""
    h, dk, dv = _linear_dims(cfg)
    return 2 * state_bytes(cfg, rows) + rows * h * (4 * dk + 2 * dv) * 4
