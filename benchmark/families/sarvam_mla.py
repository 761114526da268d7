"""Sarvam-105B (latent attention over a cache of one 576-wide row a token; a
leading dense layer, then 128 sigmoid-routed experts top-8 beside a shared
expert): the config file's published keys -> `SarvamMlaConfig` arguments,
the program's model for them, the parameters it holds and those that
multiply, and what its two latent-attention kernels have to move and multiply
(for their roofline shares). `references/sarvam_mla.py` holds the family's
plain reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.sarvam_mla"
# Lanes of a tile of the device's memory: a minor axis is padded to them.
LANES = 128


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the sarvam_mla family")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/sarvam_mla.py has an untied head only")
    if cfg.get("hidden_act") != "silu" or not cfg.get("use_qk_norm"):
        raise ValueError("models/sarvam_mla.py: SiLU, and the norms "
                         "`use_qk_norm` stands for")
    if cfg.get("q_lora_rank"):
        raise ValueError("models/sarvam_mla.py has no compressed query")
    if cfg["q_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
            or cfg["head_dim"] != cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]:
        raise ValueError("q_head_dim is not nope + rope, or head_dim not "
                         "the latent beside the rotary key")
    if not cfg.get("moe_router_enable_expert_bias"):
        raise ValueError("models/sarvam_mla.py routes by sigmoid scores "
                         "under a bias on the choice")
    scaling = cfg["rope_scaling"]
    if scaling["type"] != "deepseek_yarn" or cfg["rope_theta"] != cfg[
            "default_theta"]:
        raise ValueError("models/sarvam_mla.py: deepseek_yarn over one base")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        # The router's columns are the published experts; this chip holds
        # the first `num_experts` (the file's, cut by `reduced`).
        "num_experts": routed(cfg),
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "experts_held": [0, cfg["num_experts"]],
        "num_shared_experts": cfg["num_shared_experts"],
        "first_k_dense_replace": cfg["first_k_dense_replace"],
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "num_layers": cfg["num_hidden_layers"],
        "num_heads": cfg["num_attention_heads"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "rope_theta": float(cfg["rope_theta"]),
        "yarn_factor": float(scaling["factor"]),
        "yarn_original_max_position_embeddings":
            scaling["original_max_position_embeddings"],
        "yarn_beta_fast": float(scaling["beta_fast"]),
        "yarn_beta_slow": float(scaling["beta_slow"]),
        "yarn_mscale": float(scaling["mscale"]),
        "yarn_mscale_all_dim": float(scaling["mscale_all_dim"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.sarvam_mla import SarvamMlaConfig, SarvamMlaModel

    return SarvamMlaModel(SarvamMlaConfig(**kw))


def routed(cfg: Dict[str, Any]) -> int:
    """Experts the router chooses among: the published count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def expert_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attention_params(cfg: Dict[str, Any]) -> int:
    """One layer's attention: the query projection, the down-projection to
    the latent and the rotary key, the up-projection to every head's keys and
    values, the output projection, and the two norms (the latent's, the query
    heads')."""
    e, h, rank = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["kv_lora_rank"])
    return (e * h * cfg["q_head_dim"] + e * cfg["head_dim"]
            + rank * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * e + rank + cfg["q_head_dim"])


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: gate and up, then down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: Dict[str, Any]) -> int:
    e = cfg["hidden_size"]
    return attention_params(cfg) + 3 * e * cfg["intermediate_size"] + 2 * e


def expert_layer_params(cfg: Dict[str, Any], held: int = None) -> int:
    """Attention, the router over every published expert and its bias, the
    `held` experts (the file's count where not given), the shared expert,
    the block's two norms."""
    e = cfg["hidden_size"]
    held = cfg["num_experts"] if held is None else held
    return (attention_params(cfg) + e * routed(cfg) + routed(cfg)
            + (held + cfg["num_shared_experts"]) * expert_params(cfg) + 2 * e)


def parameters(cfg: Dict[str, Any]) -> int:
    """Every parameter this configuration holds: its dense and its expert
    layers with the experts held here, its slice of the embedding and of the
    untied head, the final norm."""
    e = cfg["hidden_size"]
    return (cfg["first_k_dense_replace"] * dense_layer_params(cfg)
            + expert_layers(cfg) * expert_layer_params(cfg)
            + 2 * e * cfg["vocab_size"] + e)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token here:
    attention's projections, the dense MLP, the router, the shared expert,
    the share of a token's `num_experts_per_tok` experts that is held on this
    chip on average, and the head's slice."""
    e = cfg["hidden_size"]
    attn = attention_params(cfg) - cfg["kv_lora_rank"] - cfg["q_head_dim"]
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed(cfg)
    sparse = (attn + e * routed(cfg)
              + (here + cfg["num_shared_experts"]) * expert_params(cfg))
    dense = attn + 3 * e * cfg["intermediate_size"]
    return int(cfg["first_k_dense_replace"] * dense
               + expert_layers(cfg) * sparse + e * cfg["vocab_size"])


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of the published form's two matmuls per token at
    sequence length `seq`: keys of `q_head_dim`, values of `v_head_dim`."""
    pair = 2 * cfg["num_attention_heads"] * (cfg["q_head_dim"]
                                             + cfg["v_head_dim"])
    return pair * cfg["num_hidden_layers"] * seq * (0.5 if causal else 1.0)


def latent_token_bytes(cfg: Dict[str, Any], itemsize: int = 2,
                       laid_out: bool = True) -> int:
    """One token's row of one layer's cache: the latent and the rotary key,
    `head_dim` = 576 values; `laid_out`: as the device holds it, the minor
    axis padded to whole tiles of 128 lanes (640)."""
    width = cfg["head_dim"]
    if laid_out:
        width = -(-width // LANES) * LANES
    return width * itemsize


def mla_decode_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Bytes one call of `mla_decode` (one layer of one token step) cannot
    do without: the rows of the `context_tokens` tokens the active rows
    hold, as they lie in memory. The kernel reads whole pages, the queries
    and writes the output: a floor."""
    return float(context_tokens) * latent_token_bytes(cfg)


def mla_decode_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Operations of one call of `mla_decode` it cannot do without: every
    head's query against a row's 576 values, and its weight times the row's
    512, for each of `context_tokens` tokens."""
    return (2.0 * cfg["num_attention_heads"] * float(context_tokens)
            * (cfg["head_dim"] + cfg["kv_lora_rank"]))


def mla_flash_flops(cfg: Dict[str, Any], tokens: float, rows: int) -> float:
    """Operations of one call of `mla_flash` (one layer of one row of a
    prefill) it cannot do without: q k^T over 192 and p v over 128 for each
    visible (query, key) pair of a row of `tokens / rows` prompt tokens (the
    kernel is called a row at a time; equal rows is the reading's
    convention). No padding, no masked half of a diagonal block: a floor."""
    n = float(tokens) / max(rows, 1)
    return (2.0 * cfg["num_attention_heads"]
            * (cfg["q_head_dim"] + cfg["v_head_dim"]) * n * (n + 1) / 2)
