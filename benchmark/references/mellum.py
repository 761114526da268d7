"""The plain reference of Mellum 2 (the Qwen3-MoE block with sliding-window
and full attention layers mixed): float32, `jax.numpy` only, matmuls at
`highest` precision, no kernels, no cache, no rings, no sorting of tokens by
expert, nothing imported from the program.

It follows huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct's config.json;
what that file does not state is under `assumed` in
configs/mellum2-12b-a2.5b-serve.json.

    h0       E[ids]
    block    a = x + Attn_kind(RMSNorm(x));  out = a + MoE(RMSNorm(a)), eps 1e-6
    Attn     q (32 x 128), k, v (4 x 128), no bias; q and k RMSNorm over each
             head's 128 with a learned scale; rotary (rotate-half) on all 128
             channels with the layer kind's inv_freq; softmax(q k^T / sqrt(128));
             query i sees key j iff j <= i and, on a `sliding_attention` layer,
             i - j < sliding_window; 8 query heads a K/V head; W_o
    rotary   sliding: inv_freq_j = theta^(-2j/128). full (YaRN): with f_j that,
             corr(r) = 128 ln(original / (2 pi r)) / (2 ln theta), low =
             floor(corr(beta_fast)), high = ceil(corr(beta_slow)), ramp_j =
             clip((j - low) / (high - low), 0, 1): inv_freq_j = f_j / factor *
             ramp_j + f_j (1 - ramp_j); cos and sin times attention_factor
    MoE      p = softmax(W_r u) over the 64 experts; the 8 largest, divided by
             their sum; sum_e w_e W_down,e(silu(W_gate,e u) * W_up,e u): every
             expert over every token, weight 0 where it was not chosen (a scan
             over the experts, one upcast at a time)
    head     RMSNorm, W_head (untied)

Departures from a textbook forward, none of which changes a value: the
queries are taken `QUERY_BLOCK` at a time against all the keys (the scores of
4,112 positions and 32 heads at once would be 2.2 GB a layer), and the head
runs on the rows asked for only.

It reads the program's parameter tree (flax names, HF's projections, the
experts as stacks `gate_up` [E,H,2I] and `down` [E,I,H]) upcast to float32,
and nothing else of the program."""

from __future__ import annotations

import math
from typing import Any, Dict

QUERY_BLOCK = 256
SLIDING = "sliding_attention"


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def inv_freq(kw: Dict[str, Any], kind: str):
    """([head_dim / 2] inverse frequencies, factor on cos and sin) of a layer
    kind, from the equations above."""
    import jax.numpy as jnp

    d, theta = kw["head_dim"], float(kw["rope_theta"])
    j = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * j / d)
    if kind == SLIDING:
        return f, 1.0
    original = kw["yarn_original_max_position_embeddings"]
    corr = lambda r: d * math.log(original / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low = max(math.floor(corr(kw["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(kw["yarn_beta_slow"])), d - 1)
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return (f / kw["yarn_factor"] * ramp + f * (1.0 - ramp),
            float(kw["yarn_attention_factor"]))


def _rope(x, freqs, factor):
    """x [S,h,d] at positions 0..S-1: rotate-half rotary embedding."""
    import jax.numpy as jnp

    s, _, d = x.shape
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # [S, d/2]
    cos = factor * jnp.cos(angles)[:, None, :]
    sin = factor * jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, window):
    """q [S,h,d], k and v [S,hk,d] -> [S,h,d]: causal softmax attention,
    inside `window` where it is not None, the queries a block at a time."""
    import jax
    import jax.numpy as jnp

    s, h, d = q.shape
    k, v = (jnp.repeat(t, h // k.shape[1], axis=1) for t in (k, v))
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, h, d)
    key = jnp.arange(s)[None, :]

    def block(args):
        qi, first = args
        # (a padded query past the end stands on the last position)
        i = jnp.minimum(first + jnp.arange(QUERY_BLOCK), s - 1)[:, None]
        visible = key <= i
        if window is not None:
            visible &= i - key < window
        scores = jnp.einsum("qhd,khd->hqk", qi, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", w, v)

    out = jax.lax.map(block, (qb, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, h, d)[:s]


def _moe(p, x, kw, f32):
    """x [T,H] -> [T,H]: each token's top_k experts by plain indexing into a
    [T,E] weight table, then every expert over every token."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    probs = jax.nn.softmax(x @ f32(p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, kw["num_experts_per_tok"])
    table = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], chosen].set(
        top / jnp.sum(top, axis=-1, keepdims=True))
    inter = p["down"].shape[1]

    def expert(y, e):
        gate_up, down, w = e
        gu = x @ f32(gate_up)
        act = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        return y + w[:, None] * (act @ f32(down)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["gate_up"], p["down"], table.T))
    return y


def hidden(params, ids, kw: Dict[str, Any]):
    """ids [S] -> the last layer's output [S, H], before the final norm."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps = kw["rms_norm_eps"]
    h, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    s = ids.shape[0]
    x = f32(params["embed_tokens"]["embedding"])[ids]  # [S,H]
    for i, kind in enumerate(kw["layer_types"]):
        p = params[f"layers_{i}"]
        a = p["self_attn"]
        u = _rms(x, f32(p["input_layernorm"]["scale"]), eps)
        proj = lambda name, heads: (u @ f32(a[name]["kernel"])).reshape(
            s, heads, d)
        freqs, factor = inv_freq(kw, kind)
        q = _rope(_rms(proj("q_proj", h), f32(a["q_norm"]["scale"]), eps),
                  freqs, factor)
        k = _rope(_rms(proj("k_proj", hk), f32(a["k_norm"]["scale"]), eps),
                  freqs, factor)
        mixed = _attend(q, k, proj("v_proj", hk),
                        kw["sliding_window"] if kind == SLIDING else None)
        x = x + mixed.reshape(s, h * d) @ f32(a["o_proj"]["kernel"])
        u = _rms(x, f32(p["post_attention_layernorm"]["scale"]), eps)
        x = x + _moe(p["mlp"], u, kw, f32)
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
