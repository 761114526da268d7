"""The plain reference of the Jamba block: float32, `jax.numpy` only, matmuls
at `highest` precision, no kernels, no cache, no chunks of the recurrence,
nothing imported from the program.

It follows huggingface.co/ai21labs/AI21-Jamba2-3B's config.json and HF's
`modeling_jamba.py` (its slow path, the one that states the mathematics):

    layer i        attention where i % attn_layer_period == attn_layer_offset,
                   Mamba otherwise; every feed-forward the dense SwiGLU MLP
                   (num_experts 1)
    block          h = x + mixer(rmsnorm(x));  out = h + mlp(rmsnorm(h))
    attention      q of num_heads heads, k and v of num_kv_heads, each query
                   head over the K/V head of its group; dense causal softmax,
                   scale head_dim^-0.5, no bias, no positional encoding
    mamba mixer    (x, z) = split(in_proj u);  x = silu(conv4(x) + b_conv)
                   (causal, depthwise, as three shifted adds);
                   (dt, B, C) = split(x_proj x), each RMS-normed with a
                   learned scale;  dt = softplus(dt_proj dt + b_dt);
                   A = -exp(A_log) [d_state, d_inner];
                   h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t;
                   y_t = h_t^T C_t + D x_t   (a `lax.scan` over positions,
                   h zero at first);  out_proj(y silu(z))
    head           rmsnorm, then the embedding transposed

It reads the program's parameter tree (flax names, HF's projections) upcast
to float32, and nothing else of the program. `logprobs(..., rows=)` runs the
head on some positions only, so that a prompt of two thousand positions over a
vocabulary of 65,536 fits beside the weights; the layers are computed whole
(a layer's largest array is [positions, 2 * d_inner] float32)."""

from __future__ import annotations

from typing import Any, Dict


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _conv_silu(x, taps, bias):
    """x [S, C], taps [4, C]: y_t = sum_j taps[j] x_{t-3+j} + bias, SiLU."""
    import jax
    import jax.numpy as jnp

    width = taps.shape[0]
    y = x * taps[width - 1] + bias
    for back in range(1, width):
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:back]), x[:-back]], axis=0)
        y = y + shifted * taps[width - 1 - back]
    return jax.nn.silu(y)


def _mamba_mixer(p, u, kw, f32):
    import jax
    import jax.numpy as jnp

    eps = kw["rms_norm_eps"]
    n, rank = kw["mamba_d_state"], kw["mamba_dt_rank"]
    x, z = jnp.split(u @ f32(p["in_proj"]["kernel"]), 2, axis=-1)
    x = _conv_silu(x, f32(p["conv1d_weight"]), f32(p["conv1d_bias"]))
    dt, b, c = jnp.split(x @ f32(p["x_proj"]["kernel"]), [rank, rank + n],
                         axis=-1)
    dt = _rms(dt, f32(p["dt_layernorm"]["scale"]), eps)
    b = _rms(b, f32(p["b_layernorm"]["scale"]), eps)
    c = _rms(c, f32(p["c_layernorm"]["scale"]), eps)
    dt = jax.nn.softplus(dt @ f32(p["dt_proj"]["kernel"]) + f32(p["dt_bias"]))
    a = -jnp.exp(f32(p["A_log"]))  # [N, D]

    def token(h, xs):
        xt, dtt, bt, ct = xs  # [D], [D], [N], [N]
        h = jnp.exp(dtt[None, :] * a) * h + (dtt * xt)[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros_like(a), (x, dt, b, c))
    y = y + f32(p["D"]) * x
    return (y * jax.nn.silu(z)) @ f32(p["out_proj"]["kernel"])


def _attention(p, x, kw, f32):
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    q = (x @ f32(p["q_proj"]["kernel"])).reshape(s, hk, h // hk, d)
    k = (x @ f32(p["k_proj"]["kernel"])).reshape(s, hk, d)
    v = (x @ f32(p["v_proj"]["kernel"])).reshape(s, hk, d)
    pos = jnp.arange(s)
    w = jnp.einsum("qgrd,kgd->grqk", q, k) * d ** -0.5
    w = jax.nn.softmax(
        jnp.where((pos[None, :] <= pos[:, None])[None, None], w, -jnp.inf),
        -1)
    o = jnp.einsum("grqk,kgd->qgrd", w, v).reshape(s, h * d)
    return o @ f32(p["o_proj"]["kernel"])


def hidden(params: Dict[str, Any], ids, kw: Dict[str, Any]):
    """ids [S] -> the float32 stream [S, hidden] after the last layer and the
    final norm, of one sequence, causal."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps = kw["rms_norm_eps"]
    period, offset = kw["attn_layer_period"], kw["attn_layer_offset"]
    x = f32(params["embed_tokens"]["embedding"])[ids]
    for i in range(kw["num_layers"]):
        p = params[f"layers_{i}"]
        normed = _rms(x, f32(p["input_layernorm"]["scale"]), eps)
        if i % period == offset:
            x = x + _attention(p["self_attn"], normed, kw, f32)
        else:
            x = x + _mamba_mixer(p["mamba"], normed, kw, f32)
        m = p["feed_forward"]
        normed = _rms(x, f32(p["pre_ff_layernorm"]["scale"]), eps)
        gate = normed @ f32(m["gate_proj"]["kernel"])
        up = normed @ f32(m["up_proj"]["kernel"])
        x = x + (jax.nn.silu(gate) * up) @ f32(m["down_proj"]["kernel"])
    return _rms(x, f32(params["final_layernorm"]["scale"]), eps)


def logits(params: Dict[str, Any], ids, kw: Dict[str, Any], rows=None):
    """ids [S] -> float32 logits [S, vocab] (of positions `rows` if given)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, kw)
        if rows is not None:
            x = x[rows]
        return x @ jnp.asarray(params["embed_tokens"]["embedding"],
                               jnp.float32).T


def logprobs(params, ids, kw, rows=None):
    import jax

    return jax.nn.log_softmax(logits(params, ids, kw, rows), axis=-1)
