"""The plain reference of Sarvam-105B (latent attention, a leading dense layer,
sigmoid-routed experts beside a shared expert): float32, `jax.numpy` only,
matmuls at `highest` precision, no kernels, no cache, no absorption (every
position's keys and values are up-projected from its latent, as published),
no sorting of tokens by expert, nothing imported from the program.

It follows huggingface.co/sarvamai/sarvam-105b's config.json (DeepSeek-V2's
keys and, where the config is silent, its modelling code); what that file
does not state is under `assumed` in configs/sarvam-105b-serve.json.

    h0       E[ids]
    block    a = x + Attn(RMSNorm(x));  out = a + FFN(RMSNorm(a)), eps 1e-6
    Attn     u W_q -> 64 heads of 192, RMSNorm over each head's 192 with a
             learned scale, = [q_nope 128 | q_rope 64];  u W_kva = [c_raw 512 |
             k_raw 64];  c = RMSNorm(c_raw);  c W_kvb -> 64 heads of [k_nope 128
             | v 128];  k_h = [k_nope_h | rot(k_raw)], the rotated key shared
             by all heads;  softmax(scale [q_nope | rot(q_rope)] . k_h), causal;
             times v_h;  W_o over the 64 x 128.  scale = 192^-0.5 m^2, m = 0.1
             mscale_all_dim ln(factor) + 1
    rotary   rotate-half over the 64 rotary values, 32 pairs: f_i =
             theta^(-2i/64); corr(r) = 64 ln(original / (2 pi r)) / (2 ln
             theta), low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)),
             g_i = 1 - clip((i - low) / (high - low), 0, 1): inv_freq_i = (f_i /
             factor)(1 - g_i) + f_i g_i; cos and sin times
             (0.1 mscale ln factor + 1) / (0.1 mscale_all_dim ln factor + 1)
    FFN      layer < first_k_dense_replace: W_down(silu(W_gate u) * W_up u);
             else shared(u) + routed(u): s = sigmoid(W_r u) over all 128
             experts; the 8 largest of s + b chosen; w_e = routed_scaling_factor
             s_e / sum_chosen s; sum over the experts HELD here (`experts_held`
             = first, count: the share of one chip) of w_e expert_e(u), every
             held expert over every token, weight 0 where it was not chosen (a
             scan over the experts, one upcast at a time)
    head     RMSNorm, W_head (untied), over this chip's slice of the vocabulary

Departures from a textbook forward, none of which changes a value: the
queries are taken `QUERY_BLOCK` at a time against all the keys (the scores of
4,112 positions and 64 heads at once would be 4.3 GB a layer), and the head
runs on the rows asked for only.

It reads the program's parameter tree (flax names, HF's projections as plain
arrays under `self_attn`, the experts as stacks `gate_up` [E,H,2I] and `down`
[E,I,H]) upcast to float32, and nothing else of the program."""

from __future__ import annotations

import math
from typing import Any, Dict

QUERY_BLOCK = 256


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _mscale(kw: Dict[str, Any], m: float) -> float:
    return 0.1 * m * math.log(kw["yarn_factor"]) + 1.0


def softmax_scale(kw: Dict[str, Any]) -> float:
    d = kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
    return d ** -0.5 * _mscale(kw, kw["yarn_mscale_all_dim"]) ** 2


def inv_freq(kw: Dict[str, Any]):
    """([qk_rope_head_dim / 2] inverse frequencies, factor on cos and sin),
    from the equations above."""
    import jax.numpy as jnp

    d, theta = kw["qk_rope_head_dim"], float(kw["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    original = kw["yarn_original_max_position_embeddings"]
    corr = lambda r: d * math.log(original / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low = max(math.floor(corr(kw["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(kw["yarn_beta_slow"])), d - 1)
    g = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / kw["yarn_factor"] * (1.0 - g) + f * g,
            _mscale(kw, kw["yarn_mscale"])
            / _mscale(kw, kw["yarn_mscale_all_dim"]))


def _rope(x, freqs, factor):
    """x [S,h,d] at positions 0..S-1: rotate-half rotary embedding."""
    import jax.numpy as jnp

    s, _, d = x.shape
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # [S, d/2]
    cos = factor * jnp.cos(angles)[:, None, :]
    sin = factor * jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, scale):
    """q, k [S,h,192], v [S,h,128] -> [S,h,128]: causal softmax attention,
    the queries a block at a time."""
    import jax
    import jax.numpy as jnp

    s, h, d = q.shape
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, h, d)
    key = jnp.arange(s)[None, :]

    def block(args):
        qi, first = args
        # (a padded query past the end stands on the last position)
        i = jnp.minimum(first + jnp.arange(QUERY_BLOCK), s - 1)[:, None]
        scores = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        w = jax.nn.softmax(jnp.where((key <= i)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", w, v)

    out = jax.lax.map(block, (qb, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, h, v.shape[-1])[:s]


def _swiglu(p, x, f32):
    import jax

    gate = x @ f32(p["gate_proj"]["kernel"])
    up = x @ f32(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ f32(p["down_proj"]["kernel"])


def routed(p, x, kw, f32):
    """x [T,H] -> [T,H]: the held experts' part of the routed sum. Each
    token's top_k experts by plain indexing into a [T,E] weight table over
    all the router's columns, then every held expert over every token."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    scores = jax.nn.sigmoid(x @ f32(p["router"]))
    _, chosen = jax.lax.top_k(scores + f32(p["bias"]),
                              kw["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    table = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(
        kw["routed_scaling_factor"] * picked
        / jnp.sum(picked, axis=-1, keepdims=True))
    first, count = kw["experts_held"]
    inter = p["down"].shape[1]

    def expert(y, e):
        gate_up, down, w = e
        gu = x @ f32(gate_up)
        act = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        return y + w[:, None] * (act @ f32(down)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["gate_up"], p["down"],
                         table.T[first:first + count]))
    return y


def hidden(params, ids, kw: Dict[str, Any]):
    """ids [S] -> the last layer's output [S, H], before the final norm."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps = kw["rms_norm_eps"]
    h, nope, rot, dv, rank = (kw["num_heads"], kw["qk_nope_head_dim"],
                              kw["qk_rope_head_dim"], kw["v_head_dim"],
                              kw["kv_lora_rank"])
    s = ids.shape[0]
    freqs, factor = inv_freq(kw)
    scale = softmax_scale(kw)
    x = f32(params["embed_tokens"]["embedding"][ids])  # [S,H]
    for i in range(kw["num_layers"]):
        p = params[f"layers_{i}"]
        a = p["self_attn"]
        u = _rms(x, f32(p["input_layernorm"]["scale"]), eps)
        q = _rms((u @ f32(a["q_proj"])).reshape(s, h, nope + rot),
                 f32(a["q_norm"]["scale"]), eps)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], freqs, factor)], axis=-1)
        ckr = u @ f32(a["kv_a_proj_with_mqa"])
        c = _rms(ckr[:, :rank], f32(a["kv_a_layernorm"]["scale"]), eps)
        k_rope = _rope(ckr[:, None, rank:], freqs, factor)     # [S,1,rot]
        kv = (c @ f32(a["kv_b_proj"])).reshape(s, h, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (s, h, rot))], axis=-1)
        mixed = _attend(q, k, kv[..., nope:], scale)
        x = x + mixed.reshape(s, h * dv) @ f32(a["o_proj"])
        u = _rms(x, f32(p["post_attention_layernorm"]["scale"]), eps)
        if i < kw["first_k_dense_replace"]:
            x = x + _swiglu(p["mlp"], u, f32)
        else:
            x = x + routed(p["mlp"], u, kw, f32) + _swiglu(
                p["shared_experts"], u, f32)
    return x


def logprobs(params: Dict[str, Any], ids, kw: Dict[str, Any], rows=None):
    """ids [S] -> float32 [S, vocab]; row r: the distribution of position
    r + 1 given ids[0..r]. `rows` [n]: only those rows, [n, vocab]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, kw)
        if rows is not None:
            x = x[rows]
        x = _rms(x, jnp.asarray(params["norm"]["scale"], jnp.float32),
                 kw["rms_norm_eps"])
        logits = x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)
        return jax.nn.log_softmax(logits, axis=-1)
