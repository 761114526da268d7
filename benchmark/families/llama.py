"""Llama-family decoder (Mistral, Yi, DeepSeek-LLM share the block): the
config file's published keys -> `LlamaConfig` arguments, and the
parameters that multiply (for operation counts)."""

from typing import Any, Dict


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if cfg.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding-window attention")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/llama.py has an untied head only")
    head_dim = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "num_layers": cfg["num_hidden_layers"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": head_dim,
        "rope_theta": float(cfg["rope_theta"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections and the output head. The embedding is a gather."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * f
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of attention's two matmuls (scores, values) per
    token at sequence length `seq`; causal attention needs half."""
    d = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    full = 2 * 2 * cfg["num_attention_heads"] * d * seq
    return cfg["num_hidden_layers"] * full * (0.5 if causal else 1.0)
