"""Mellum 2 (the Qwen3-MoE block, 64 experts routed top-8 on every layer,
sliding-window attention on three layers of four and full attention under
YaRN on the fourth): the config file's published keys -> `MellumConfig`
arguments, the program's model for them, the parameters it holds and those
that multiply, and what its two windowed kernels have to move and multiply
(for their roofline shares). `references/mellum.py` holds the family's plain
reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.mellum"
SLIDING, FULL = "sliding_attention", "full_attention"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the mellum family")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/mellum.py has an untied head only")
    if cfg.get("attention_bias"):
        raise ValueError("models/mellum.py has no attention bias")
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("models/mellum.py has no dense layer")
    if not cfg.get("norm_topk_prob"):
        raise ValueError("models/mellum.py renormalises the top-k weights")
    if not cfg.get("use_sliding_window") or cfg.get("hidden_act") != "silu":
        raise ValueError("models/mellum.py: a sliding window, and SiLU")
    sliding = cfg["rope_parameters"][SLIDING]
    full = cfg["rope_parameters"][FULL]
    if (sliding["rope_type"] != "default" or full["rope_type"] != "yarn"
            or sliding["rope_theta"] != full["rope_theta"]
            or full.get("truncate", True) is not True):
        raise ValueError("models/mellum.py: plain rotary positions on the "
                         "sliding layers, YaRN (truncated range) on the "
                         "full ones, one base")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "num_experts": cfg["num_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "layer_types": layer_types(cfg),
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "sliding_window": cfg["sliding_window"],
        "rope_theta": float(full["rope_theta"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original_max_position_embeddings":
            full["original_max_position_embeddings"],
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": float(full["attention_factor"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.mellum import MellumConfig, MellumModel

    return MellumModel(MellumConfig(**kw))


def layer_types(cfg: Dict[str, Any]):
    """The kinds of the layers that are run: the first `num_hidden_layers`
    of the published order (`layer_types` is followed, not
    `max_window_layers`)."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def sliding_layers(cfg: Dict[str, Any]) -> int:
    return layer_types(cfg).count(SLIDING)


def full_layers(cfg: Dict[str, Any]) -> int:
    return layer_types(cfg).count(FULL)


def attention_params(cfg: Dict[str, Any]) -> int:
    """One attention layer of either kind: q and o, k and v, the two head
    norms."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * e * d * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"]) + 2 * d)


def expert_params(cfg: Dict[str, Any]) -> int:
    """One expert: gate and up, then down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: Dict[str, Any]) -> int:
    """Attention, the router, every expert, the block's two norms."""
    e = cfg["hidden_size"]
    return (attention_params(cfg) + e * cfg["num_experts"]
            + cfg["num_experts"] * expert_params(cfg) + 2 * e)


def parameters(cfg: Dict[str, Any]) -> int:
    """Every parameter this configuration holds: its layers, the embedding,
    the untied head, the final norm."""
    e = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * e * cfg["vocab_size"] + e)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    attention projections, the router, the `num_experts_per_tok` experts a
    token goes through (not the 64 a layer holds) and the output head."""
    e = cfg["hidden_size"]
    layer = (attention_params(cfg) - 2 * cfg["head_dim"]
             + e * cfg["num_experts"]
             + cfg["num_experts_per_tok"] * expert_params(cfg))
    return cfg["num_hidden_layers"] * layer + e * cfg["vocab_size"]


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of softmax attention's two matmuls per token at
    sequence length `seq`: a full layer's token sees `seq` keys (half on
    average under the causal mask), a sliding layer's the same up to the
    window and `sliding_window` past it."""
    pair = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    seen = seq * (0.5 if causal else 1.0)
    return pair * (full_layers(cfg) * seen + sliding_layers(cfg)
                   * min(seen, cfg["sliding_window"]))


def kv_token_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one token of one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def swa_decode_bytes(cfg: Dict[str, Any], window_tokens: float) -> float:
    """Bytes one call of `swa_decode` (one sliding layer of one token step)
    cannot do without: K and V of the `window_tokens` tokens inside the
    window, summed over the rows (`min(length, window)` a row). The kernel
    reads whole pages (up to a page more at each end of a window), the
    queries and the output: a floor."""
    return float(window_tokens) * kv_token_bytes(cfg)


def swa_flash_flops(cfg: Dict[str, Any], tokens: float, rows: int) -> float:
    """Operations of one call of `swa_flash` (one sliding layer of one
    prefill) it cannot do without: q k^T and p v, 4 x heads x head_dim for
    each visible (query, key) pair of `tokens` prompt tokens in `rows` rows
    of equal length (the fewest pairs any split of the tokens gives is not
    claimed: equal rows is the reading's convention). A row of n tokens has
    n (n + 1) / 2 pairs up to the window and window x (n - (window - 1) / 2)
    past it. No padding, no masked half of a diagonal block: a floor."""
    w = cfg["sliding_window"]
    n = float(tokens) / max(rows, 1)
    pairs = n * (n + 1) / 2 if n <= w else (
        w * (w + 1) / 2 + (n - w) * w)
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * rows * pairs
