"""The plain reference of the Llama-family block: float32, `jax.numpy`
only, matmuls at `highest` precision (on a TPU a float32 matmul otherwise
runs in bf16 passes), no kernels, no cache, no batching tricks.

It follows the published description of Llama/Mistral: pre-norm residual
blocks, RMSNorm with float32 accumulation, rotary embedding on the two
halves of each head (the layout `models/llama.py` and HF share), grouped
query attention, SwiGLU, an untied output head. It reads the program's
parameter tree (flax names) and nothing else of the program."""

from __future__ import annotations

import math
from typing import Any, Dict


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * freqs  # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(params: Dict[str, Any], ids, kw: Dict[str, Any]):
    """ids [S] -> float32 logits [S, vocab] of one sequence, causal."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    s = ids.shape[0]
    pos = jnp.arange(s)
    heads, kv_heads = kw["num_heads"], kw["num_kv_heads"]
    causal = pos[None, :] <= pos[:, None]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"]["embedding"])[ids]
        for i in range(kw["num_layers"]):
            p = params[f"layers_{i}"]
            a = p["self_attn"]
            h = _rms(x, f32(p["input_layernorm"]["scale"]),
                     kw["rms_norm_eps"])
            q = jnp.einsum("se,ehd->shd", h, f32(a["q_proj"]["kernel"]))
            k = jnp.einsum("se,ehd->shd", h, f32(a["k_proj"]["kernel"]))
            v = jnp.einsum("se,ehd->shd", h, f32(a["v_proj"]["kernel"]))
            q = _rope(q, pos, kw["rope_theta"])
            k = _rope(k, pos, kw["rope_theta"])
            k = jnp.repeat(k, heads // kv_heads, axis=1)
            v = jnp.repeat(v, heads // kv_heads, axis=1)
            w = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
            w = jax.nn.softmax(jnp.where(causal[None], w, -jnp.inf), -1)
            o = jnp.einsum("hqk,khd->qhd", w, v)
            x = x + jnp.einsum("qhd,hde->qe", o, f32(a["o_proj"]["kernel"]))
            h = _rms(x, f32(p["post_attention_layernorm"]["scale"]),
                     kw["rms_norm_eps"])
            m = p["mlp"]
            gate = h @ f32(m["gate_proj"]["kernel"])
            up = h @ f32(m["up_proj"]["kernel"])
            x = x + (jax.nn.silu(gate) * up) @ f32(m["down_proj"]["kernel"])
        x = _rms(x, f32(params["norm"]["scale"]), kw["rms_norm_eps"])
        return x @ f32(params["lm_head"]["kernel"])


def logprobs(params, ids, kw):
    import jax

    return jax.nn.log_softmax(logits(params, ids, kw), axis=-1)


def next_token_loss(params, ids, kw):
    """Mean cross entropy of predicting ids[t + 1] from ids[: t + 1], over
    a batch [B, S], one sequence at a time (the float32 logits of a whole
    batch would not fit beside a training state)."""
    import jax
    import jax.numpy as jnp

    def one(seq):
        lp = logprobs(params, seq, kw)[:-1]
        return -jnp.take_along_axis(lp, seq[1:, None], axis=-1).mean()

    return jnp.mean(jax.lax.map(one, ids))
