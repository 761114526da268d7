"""The plain reference of MiniCPM-SALA (lightning linear-attention layers
beside NoPE sparse-attention layers under MiniCPM's three multipliers):
float32, `jax.numpy` only, matmuls at `highest` precision, no kernels, no
cache, no chunkwise form and no state (a lightning layer is its causal sum
under the decay matrix), no segment means (a compressed key is the mean of
its 32 keys), nothing imported from the program.

It follows huggingface.co/openbmb/MiniCPM-SALA's config.json; what that file
does not state is under `assumed` in configs/minicpm-sala-serve.json.

    x0        scale_emb E[ids]
    block     a = x + s mixer(RMSNorm(x));  out = a + s Mlp(RMSNorm(a)),
              s = scale_depth / sqrt(depth), eps 1e-6, Mlp = W_down(silu(W_gate
              u) * W_up u)
    lightning q, k, v = u W_q, u W_k, u W_v, heads of 128;  RMSNorm with a
              learned scale over each head of q and of k;  both rotated
              (rotate-half, theta^(-2i/128));  o_t = 128^-0.5 sum_(j<=t)
              lambda_h^(t-j) (q_t . k_j) v_j,  lambda_h = exp(-2^(-8 (h+1) /
              H));  y = RMSNorm_hidden(concat_h o) * sigmoid(u W_g);  y W_o
    minicpm4  q heads of 128 on `num_kv_heads` KV heads (head h reads KV head
              h // (H / HK)), RMSNorm over each head of q and k, no rotation,
              scale 128^-0.5.  The query at position t:
              t + 1 < dense_len: softmax over keys 0..t;
              else, a KV head g at a time: c_i = mean(k_g[16 i .. 16 i + 31]),
              seen iff 16 i + 31 <= t;  p_h = softmax_i(scale q_h . c_i) over
              the seen;  r_i = sum_(h in g) p_(h,i);  block j (keys 64 j .. 64
              j + 63): b_j = max r_i over the seen i in 4 j - 1 .. 4 j + 3;
              block 0 and the 32 blocks that end with the query's own are
              chosen, blocks past its own are out;  the 64 largest b_j (the
              chosen among them, ties to the lower index);  softmax over the
              keys j <= t of those blocks.
              o * sigmoid(u W_g);  W_o
    head      RMSNorm, / (hidden / dim_model_base), W_head (untied)

Departures from a textbook forward, none of which changes a value: the
queries of both mixers are taken `QUERY_BLOCK` at a time against all the
keys (the scores of 17k positions and 32 heads at once would be 39 GB a
layer), and the head runs on the rows asked for only.

It reads the program's parameter tree (flax names, HF's projections under
`self_attn`) upcast to float32, and nothing else of the program."""

from __future__ import annotations

import math
from typing import Any, Dict

QUERY_BLOCK = 256
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S,h,d] at positions 0..S-1: rotate-half rotary embedding."""
    import jax.numpy as jnp

    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _query_blocks(q):
    """q [S,h,d] -> ([blocks, QUERY_BLOCK, h, d], each block's first
    position): the last block padded with zeros."""
    import jax.numpy as jnp

    s = q.shape[0]
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    return (jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (blocks, QUERY_BLOCK) + q.shape[1:]),
        jnp.arange(blocks) * QUERY_BLOCK)


def lightning(q, k, v, heads):
    """q, k, v [S,h,d] -> [S,h,d]: o_t = sum_(j<=t) lambda^(t-j) (q_t . k_j)
    v_j, the causal sum under the decay matrix."""
    import jax
    import jax.numpy as jnp

    s = q.shape[0]
    slope = 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                    / heads)                                    # -log lambda
    key = jnp.arange(s)[None, :]

    def block(args):
        qi, first = args
        t = (first + jnp.arange(QUERY_BLOCK))[:, None]
        gap = (t - key).astype(jnp.float32)                     # [Q,S]
        decay = jnp.where(gap >= 0, jnp.exp(
            -slope[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)  # [h,Q,S]
        scores = jnp.einsum("qhd,khd->hqk", qi, k) * decay
        return jnp.einsum("hqk,khd->qhd", scores, v)

    out = jax.lax.map(block, _query_blocks(q))
    return out.reshape((-1,) + q.shape[1:])[:s]


def chosen_blocks(qi, t, k, kw):
    """qi [Q,H,D] queries at positions t [Q], k [S,HK,D] -> [HK,Q,NB] bool:
    the blocks each query's KV heads attend to once it is past dense_len
    (the equations' steps 1 to 4)."""
    import jax
    import jax.numpy as jnp

    size, stride, bs = kw["kernel_size"], kw["kernel_stride"], kw["block_size"]
    topk, per = kw["topk"], kw["block_size"] // kw["kernel_stride"]
    s, hk, d = k.shape
    h = qi.shape[1]
    nb = -(-s // bs)
    n = max((s - size) // stride + 1, 1)
    # c_i: the mean of keys stride i .. stride i + size - 1
    kp = jnp.pad(k, ((0, max(size - s, 0)), (0, 0), (0, 0)))
    comp = kp[(jnp.arange(n) * stride)[:, None] + jnp.arange(size)].mean(
        axis=1)                                                 # [N,HK,D]
    seen = (jnp.arange(n) * stride + size - 1)[None, :] <= t[:, None]  # [Q,N]
    scores = jnp.einsum("qghd,ngd->gqhn", qi.reshape(-1, hk, h // hk, d),
                        comp) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None, :, None, :], scores, -jnp.inf),
                       axis=-1)
    r = jnp.where(seen[None], jnp.sum(
        jnp.where(seen[None, :, None, :], p, 0.0), axis=2), -1.0)  # [HK,Q,N]
    # b_j: the largest r of the seen windows that overlap block j
    score = jnp.full((hk, t.shape[0], nb), -1.0)
    block = jnp.arange(nb)
    for off in range(-1, per):
        i = block * per + off
        inside = (i >= 0) & (i < n)
        score = jnp.maximum(score, jnp.where(
            inside, r[..., jnp.clip(i, 0, n - 1)], -1.0))
    own = (t // bs)[:, None]
    forced = (block < kw["init_blocks"]) | (
        (block <= own) & (block > own - kw["window_size"] // bs))
    score = jnp.where(forced, jnp.inf,
                      jnp.where(block <= own, score, -jnp.inf))
    # the topk largest, ties to the lower index
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :topk]
    picked = (order[..., None] == block).any(axis=-2)
    return picked & (block <= own)


def sparse(q, k, v, kw):
    """q [S,H,D], k, v [S,HK,D] -> [S,H,D]: the `minicpm4` mixer's attention
    as a masked dense softmax."""
    import jax
    import jax.numpy as jnp

    s, h, d = q.shape
    hk, bs = k.shape[1], kw["block_size"]
    key = jnp.arange(s)
    of_block = key // bs

    def block(args):
        qi, first = args
        # (a padded query past the end stands on the last position)
        t = jnp.minimum(first + jnp.arange(QUERY_BLOCK), s - 1)
        picked = chosen_blocks(qi, t, k, kw)                    # [HK,Q,NB]
        dense = (t + 1 < kw["dense_len"])[None, :, None]
        visible = (dense | picked[..., of_block]) & (
            key[None, None, :] <= t[None, :, None])             # [HK,Q,S]
        scores = jnp.einsum("qghd,kgd->gqhk",
                            qi.reshape(-1, hk, h // hk, d), k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(visible[:, :, None, :], scores,
                                     -jnp.inf), axis=-1)
        return jnp.einsum("gqhk,kgd->qghd", w, v).reshape(-1, h, d)

    out = jax.lax.map(block, _query_blocks(q))
    return out.reshape(-1, h, d)[:s]


def hidden(params, ids, kw: Dict[str, Any]):
    """ids [S] -> the last layer's output [S, H], before the final norm."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps, theta = kw["rms_norm_eps"], float(kw["rope_theta"])
    residual = kw["scale_depth"] / math.sqrt(kw["depth"])
    s = ids.shape[0]
    x = kw["scale_emb"] * f32(params["embed_tokens"]["embedding"][ids])
    for i, kind in enumerate(kw["mixer_types"]):
        p = params[f"layers_{i}"]
        a = p["self_attn"]
        w = lambda name: f32(a[name]["kernel"])
        u = _rms(x, f32(p["input_layernorm"]["scale"]), eps)
        if kind == LIGHTNING:
            h, d = kw["lightning_heads"], kw["lightning_head_dim"]
            hk = h
        else:
            h, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
        q = _rms((u @ w("q_proj")).reshape(s, h, d),
                 f32(a["q_norm"]["scale"]), eps)
        k = _rms((u @ w("k_proj")).reshape(s, hk, d),
                 f32(a["k_norm"]["scale"]), eps)
        v = (u @ w("v_proj")).reshape(s, hk, d)
        if kind == LIGHTNING:
            o = d ** -0.5 * lightning(_rope(q, theta), _rope(k, theta), v, h)
            o = _rms(o.reshape(s, h * d), f32(a["o_norm"]["scale"]), eps)
        else:
            o = sparse(q, k, v, kw).reshape(s, h * d)
        o = o * jax.nn.sigmoid(u @ w("g_proj"))
        x = x + residual * (o @ w("o_proj"))
        u = _rms(x, f32(p["post_attention_layernorm"]["scale"]), eps)
        m = p["mlp"]
        gate = u @ f32(m["gate_proj"]["kernel"])
        up = u @ f32(m["up_proj"]["kernel"])
        x = x + residual * ((jax.nn.silu(gate) * up)
                            @ f32(m["down_proj"]["kernel"]))
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
                 kw["rms_norm_eps"]) / (kw["hidden_size"]
                                        / kw["dim_model_base"])
        logits = x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)
        return jax.nn.log_softmax(logits, axis=-1)
