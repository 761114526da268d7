"""Nemotron-H (Nemotron 3 Super: every block ONE mixer, a Mamba-2 with
groups of B and C, grouped-query attention without positions, or sigmoid-routed
relu^2 experts in a latent beside a shared expert): the config file's
published keys -> `NemotronHConfig` arguments, the program's model for them,
the parameters it holds and those that multiply, and what its grouped matmul
and its whole decode step have to move (for their roofline shares).
`references/nemotron_h.py` holds the family's plain reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.nemotron_h"
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the nemotron_h family")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("models/nemotron_h.py has an untied head only")
    if (cfg.get("attention_bias") or cfg.get("mlp_bias")
            or cfg.get("use_bias") or cfg.get("mamba_proj_bias")
            or not cfg.get("use_conv_bias")):
        raise ValueError("models/nemotron_h.py: a convolution bias, and no "
                         "bias on any projection")
    if (cfg.get("mlp_hidden_act") != "relu2"
            or cfg.get("mamba_hidden_act") != "silu"):
        raise ValueError("models/nemotron_h.py: relu^2 experts and SiLU in "
                         "the Mamba-2 mixer")
    if cfg.get("n_group") != 1 or cfg.get("topk_group") != 1:
        raise ValueError("models/nemotron_h.py: the router chooses among all "
                         "experts (one group of them)")
    if not cfg.get("norm_topk_prob") or cfg.get("n_shared_experts") != 1:
        raise ValueError("models/nemotron_h.py: renormalised top-k weights "
                         "and one shared expert")
    if cfg["expand"] * cfg["hidden_size"] != _d_inner(cfg):
        raise ValueError("mamba_num_heads * mamba_head_dim is not expand * "
                         "hidden_size")
    if cfg.get("moe_intermediate_size") != cfg.get("intermediate_size"):
        raise ValueError("models/nemotron_h.py: one width for the routed "
                         "experts")
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "hybrid_override_pattern": pattern(cfg),
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "mamba_n_heads": cfg["mamba_num_heads"],
        "mamba_d_head": cfg["mamba_head_dim"],
        "mamba_d_state": cfg["ssm_state_size"],
        "mamba_n_groups": cfg["n_groups"],
        "mamba_d_conv": cfg["conv_kernel"],
        "mamba_chunk_size": cfg["chunk_size"],
        # The router's columns are the published experts; this chip holds
        # the first `n_routed_experts` (the file's, cut by `reduced`).
        "num_experts": _routed(cfg),
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "experts_held": [0, cfg["n_routed_experts"]],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "moe_latent_size": cfg["moe_latent_size"],
        "shared_intermediate_size":
            cfg["moe_shared_expert_intermediate_size"],
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "rms_norm_eps": float(cfg["layer_norm_epsilon"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    return NemotronHModel(NemotronHConfig(**kw))


def pattern(cfg: Dict[str, Any]) -> str:
    """The kinds of the blocks that are run: the first `num_hidden_layers`
    letters of the published order."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def blocks(cfg: Dict[str, Any], kind: str) -> int:
    return pattern(cfg).count(kind)


def _routed(cfg: Dict[str, Any]) -> int:
    """Experts the router chooses among: the published count."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def _d_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def _conv_dim(cfg: Dict[str, Any]) -> int:
    return _d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mixer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one Mamba-2 mixer, by tensor."""
    e, d, heads = cfg["hidden_size"], _d_inner(cfg), cfg["mamba_num_heads"]
    conv = _conv_dim(cfg)
    return {"in_proj": e * (d + conv + heads),
            "conv1d": conv * cfg["conv_kernel"] + conv,
            "A_log": heads, "D": heads, "dt_bias": heads, "norm": d,
            "out_proj": d * e}


def _attention_proj(cfg: Dict[str, Any]) -> int:
    """q and o, k and v of one attention block."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * e * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert: up, then down, in the latent."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_block_rest(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one expert block outside its routed experts, by
    tensor: what every chip of the deployment holds whole."""
    e, latent = cfg["hidden_size"], cfg["moe_latent_size"]
    return {"router": e * _routed(cfg), "bias": _routed(cfg),
            "latent_proj": 2 * e * latent,
            "shared": 2 * e * cfg["moe_shared_expert_intermediate_size"]}


def parameters(cfg: Dict[str, Any]) -> int:
    """Every parameter this configuration holds: `n_routed_experts` experts
    an expert block (the file's), the router over all that are routed; a
    norm a block, the final norm, the embedding and the untied head."""
    e = cfg["hidden_size"]
    return (blocks(cfg, MAMBA) * (sum(mixer_params(cfg).values()) + e)
            + blocks(cfg, ATTENTION) * (_attention_proj(cfg) + e)
            + blocks(cfg, EXPERTS) * (
                sum(expert_block_rest(cfg).values()) + e
                + cfg["n_routed_experts"] * expert_params(cfg))
            + 2 * e * cfg["vocab_size"] + e)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token ON
    THIS CHIP: the Mamba-2 and attention projections, the router, both
    latent projections, the shared expert, the share of a token's
    `num_experts_per_tok` experts that is held here (`n_routed_experts` of
    those routed, with a router that favours none) and the head (the
    embedding is a gather). The convolution, the recurrence and the norms
    are elementwise."""
    m, rest = mixer_params(cfg), expert_block_rest(cfg)
    held = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / _routed(cfg))
    return int(blocks(cfg, MAMBA) * (m["in_proj"] + m["out_proj"])
               + blocks(cfg, ATTENTION) * _attention_proj(cfg)
               + blocks(cfg, EXPERTS) * (
                   rest["router"] + rest["latent_proj"] + rest["shared"]
                   + held * expert_params(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of softmax attention's two matmuls per token at
    sequence length `seq`: the attention blocks only."""
    full = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    return blocks(cfg, ATTENTION) * full * (0.5 if causal else 1.0)


def state_slot_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """What one Mamba-2 block keeps for one engine slot: the float32 state
    [heads, d_head, d_state] and the convolution's last inputs
    [conv_kernel - 1, d_inner + 2 G N] in the compute dtype."""
    return (_d_inner(cfg) * cfg["ssm_state_size"] * 4
            + (cfg["conv_kernel"] - 1) * _conv_dim(cfg) * itemsize)


def moe_gmm_bytes(cfg: Dict[str, Any], tokens: int,
                  experts_touched: float, itemsize: int = 2) -> float:
    """Bytes the two `moe_gmm` calls of one expert block and one forward
    have to move: the up and down weights of the held experts some token
    chose, once each, and per assignment that fell on a held expert (a
    token's `num_experts_per_tok`, the held share of them under a router
    that favours none) its latent row into the up call, the activation out
    of it and into the down call, and the latent row out. It counts no
    padding row and no weight read twice, so the kernel cannot do with
    less."""
    latent, i = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    rows = (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / _routed(cfg))
    weights = experts_touched * expert_params(cfg)
    activations = rows * (latent + i) + rows * (i + latent)
    return float(itemsize) * (weights + activations)


def decode_step_bytes(cfg: Dict[str, Any], rows: int,
                      experts_touched: float, kv_tokens: float = 0.0,
                      itemsize: int = 2) -> float:
    """Bytes one token step of the decode program has to move for the
    engine's `rows` slots (an idle slot's state is read and written too):
    every weight that multiplies, once (of the routed experts those
    `experts_touched` a block that some token chose; of the embedding the
    rows' rows), each Mamba-2 block's state and convolution tail of every
    slot read and written, the K/V of `kv_tokens` cached tokens read an
    attention block. Activations between the blocks are left out: a floor."""
    e = cfg["hidden_size"]
    m, rest = mixer_params(cfg), expert_block_rest(cfg)
    weights = (blocks(cfg, MAMBA) * (sum(m.values()) + e)
               + blocks(cfg, ATTENTION) * (_attention_proj(cfg) + e)
               + blocks(cfg, EXPERTS) * (
                   sum(rest.values()) + e
                   + experts_touched * expert_params(cfg))
               + e * cfg["vocab_size"] + e + rows * e)
    state = blocks(cfg, MAMBA) * rows * 2 * state_slot_bytes(cfg, itemsize)
    kv = (blocks(cfg, ATTENTION) * kv_tokens * 2
          * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize)
    # (the router's kernel and bias, A_log, D, dt_bias and the norms are
    # float32: four bytes, counted at `itemsize`: a floor)
    return float(itemsize) * weights + state + kv
