"""MiniCPM-SALA (lightning linear-attention layers with a decayed state a
slot beside NoPE sparse-attention layers that choose 64 key blocks by
compressed keys): the config file's published keys -> `MiniCPMSalaConfig`
arguments, the program's model for them, the parameters it holds and those
that multiply, what its caches hold a token and a slot, and what its two
sparse-attention kernels have to move and multiply (for their roofline
shares). `references/minicpm_sala.py` holds the family's plain reference."""

import importlib.util
from typing import Any, Dict

PROGRAM_MODULE = "ray_tpu.models.minicpm_sala"
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    # Before any cluster starts: a program without this family (the parent
    # of the PR that brought it) fails here, in a second.
    if importlib.util.find_spec(PROGRAM_MODULE) is None:
        raise RuntimeError(f"this program has no {PROGRAM_MODULE}: it "
                           "cannot build the minicpm_sala family "
                           "(unknown model family 'minicpm_sala')")
    if cfg.get("tie_word_embeddings") or cfg.get("attention_bias"):
        raise ValueError("models/minicpm_sala.py: an untied head, no biases")
    if cfg.get("hidden_act") != "silu" or not cfg.get("qk_norm"):
        raise ValueError("models/minicpm_sala.py: SiLU, and a norm over "
                         "each head of q and k")
    if cfg.get("attn_use_rope") or not cfg.get("lightning_use_rope"):
        raise ValueError("models/minicpm_sala.py: sparse layers without "
                         "rotary embedding, lightning layers with it")
    if not (cfg.get("use_output_gate") and cfg.get("use_output_norm")
            and cfg.get("attn_use_output_gate")):
        raise ValueError("models/minicpm_sala.py: both mixers gated, the "
                         "lightning one normalised")
    if cfg["lightning_nkv"] != cfg["lightning_nh"] \
            or cfg["lightning_scale"] != "1/sqrt(d)":
        raise ValueError("models/minicpm_sala.py: a lightning key head a "
                         "query head, scale 1/sqrt(d)")
    if len(cfg["mixer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("mixer_types does not name num_hidden_layers layers")
    sparse = cfg["sparse_config"]
    out = {
        "vocab_size": cfg["vocab_size"],
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "mixer_types": list(cfg["mixer_types"]),
        # The residual multiplier keeps the published depth.
        "depth": cfg.get("published", {}).get("num_hidden_layers",
                                              cfg["num_hidden_layers"]),
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "lightning_heads": cfg["lightning_nh"],
        "lightning_head_dim": cfg["lightning_head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "scale_emb": float(cfg["scale_emb"]),
        "scale_depth": float(cfg["scale_depth"]),
        "dim_model_base": cfg["dim_model_base"],
        "kernel_size": sparse["kernel_size"],
        "kernel_stride": sparse["kernel_stride"],
        "block_size": sparse["block_size"],
        "init_blocks": sparse["init_blocks"],
        "window_size": sparse["window_size"],
        "topk": sparse["topk"],
        "dense_len": sparse["dense_len"],
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq_len": int(cfg.get("run", {}).get(
            "max_seq_len", cfg["max_position_embeddings"])),
    }
    out.update(cfg.get("run", {}).get("model_kwargs", {}))
    return out


def model(kw: Dict[str, Any]):
    """The program's model object for the arguments `model_kwargs` made."""
    from ray_tpu.models.minicpm_sala import (MiniCPMSalaConfig,
                                             MiniCPMSalaModel)

    return MiniCPMSalaModel(MiniCPMSalaConfig(**kw))


def sparse_layers(cfg: Dict[str, Any]) -> int:
    return sum(kind == SPARSE for kind in cfg["mixer_types"])


def lightning_layers(cfg: Dict[str, Any]) -> int:
    return sum(kind == LIGHTNING for kind in cfg["mixer_types"])


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def lightning_mixer_params(cfg: Dict[str, Any]) -> int:
    """q, k, v, the output gate and o, each hidden x heads x head_dim."""
    return 5 * cfg["hidden_size"] * cfg["lightning_nh"] * cfg[
        "lightning_head_dim"]


def sparse_mixer_params(cfg: Dict[str, Any]) -> int:
    """q, the output gate and o over the query heads; k and v over the KV
    heads."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    return (3 * e * cfg["num_attention_heads"] * d
            + 2 * e * cfg["num_key_value_heads"] * d)


def lightning_layer_params(cfg: Dict[str, Any]) -> int:
    """The mixer, the MLP, the block's two norms, the head norms of q and k,
    the output norm over all the heads' channels."""
    d = cfg["lightning_head_dim"]
    return (lightning_mixer_params(cfg) + mlp_params(cfg)
            + 2 * cfg["hidden_size"] + 2 * d + cfg["lightning_nh"] * d)


def sparse_layer_params(cfg: Dict[str, Any]) -> int:
    return (sparse_mixer_params(cfg) + mlp_params(cfg)
            + 2 * cfg["hidden_size"] + 2 * cfg["head_dim"])


def vocabulary_params(cfg: Dict[str, Any]) -> int:
    """The embedding, the untied head, the final norm."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def parameters(cfg: Dict[str, Any]) -> int:
    """Every parameter this configuration holds."""
    return (lightning_layers(cfg) * lightning_layer_params(cfg)
            + sparse_layers(cfg) * sparse_layer_params(cfg)
            + vocabulary_params(cfg))


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: every
    projection and the head (not the embedding's gather, not the norms)."""
    return (lightning_layers(cfg) * (lightning_mixer_params(cfg)
                                     + mlp_params(cfg))
            + sparse_layers(cfg) * (sparse_mixer_params(cfg)
                                    + mlp_params(cfg))
            + cfg["vocab_size"] * cfg["hidden_size"])


def chosen_keys(cfg: Dict[str, Any], t: int) -> int:
    """Keys the query at position t of a sparse layer attends to: all t + 1
    while t + 1 < dense_len; from there on `topk` blocks, its own up to
    itself."""
    sparse = cfg["sparse_config"]
    if t + 1 < sparse["dense_len"]:
        return t + 1
    bs = sparse["block_size"]
    return min((sparse["topk"] - 1) * bs + t % bs + 1, t + 1)


def attention_flops_per_token(cfg: Dict[str, Any], seq: int,
                              causal: bool = True) -> float:
    """Forward operations of the mixers' own products per token at sequence
    length `seq`: a sparse layer's q k^T and p v over the keys a query
    attends to (the mean over the positions of a causal sequence), a
    lightning layer's k^T v and q S over its 128 x 128 state."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    positions = range(seq) if causal else [seq - 1]
    keys = sum(chosen_keys(cfg, t) for t in positions) / len(positions)
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return (sparse_layers(cfg) * 4.0 * h * d * keys
            + lightning_layers(cfg) * 4.0 * lh * ld * ld)


def kv_token_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """A token's keys and values over the sparse layers (the only layers
    whose cache grows with the context)."""
    return (sparse_layers(cfg) * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def index_page_bytes(cfg: Dict[str, Any]) -> int:
    """A page's segment means over the sparse layers: block_size /
    kernel_stride float32 rows of the KV heads' widths."""
    sparse = cfg["sparse_config"]
    return (sparse_layers(cfg) * sparse["block_size"]
            // sparse["kernel_stride"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 4)


def state_slot_bytes(cfg: Dict[str, Any]) -> int:
    """A slot's float32 states over the lightning layers."""
    return (lightning_layers(cfg) * cfg["lightning_nh"]
            * cfg["lightning_head_dim"] ** 2 * 4)


def sparse_decode_bytes(cfg: Dict[str, Any], pages: float) -> float:
    """Bytes one call of `sparse_decode` (one sparse layer of one token step)
    cannot do without: of each of the `pages` entries its rows' KV heads
    list (`pages_selected` a call), that KV head's keys and values. The
    queries, the lists and the output are left out: a floor."""
    sparse = cfg["sparse_config"]
    return float(pages) * sparse["block_size"] * cfg["head_dim"] * 2 * 2


def sparse_decode_flops(cfg: Dict[str, Any], pages: float) -> float:
    """Operations of one call of `sparse_decode` it cannot do without: the
    group's query heads against each listed key, and their weights against
    its value (a listed page counted whole: the one page a row that is not
    full is 1 in 64 of a row's)."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return (float(pages) * cfg["sparse_config"]["block_size"] * group
            * cfg["head_dim"] * 4.0)


def sparse_flash_flops(cfg: Dict[str, Any], tokens: float, rows: int
                       ) -> float:
    """Operations of one call of `sparse_flash` (one sparse layer of one row
    of a prefill) it cannot do without: q k^T and p v for each (query, key)
    pair a query attends to, of a row of `tokens / rows` prompt tokens (the
    kernel is called a row at a time; equal rows is the reading's
    convention). No padding, no key a tile reads and masks: a floor."""
    n = int(float(tokens) / max(rows, 1))
    pairs = sum(chosen_keys(cfg, t) for t in range(n))
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
