"""The plain reference of the Olmo-Hybrid block: float32, `jax.numpy` only,
matmuls at `highest` precision, no kernels, no cache, no chunks, nothing
imported from the program.

It follows huggingface.co/allenai/Olmo-Hybrid-7B's config.json and the gated
delta rule as arXiv:2412.06464 states it, token by token:

    linear layer   q, k, v = silu(conv4(proj(x)))  (causal, depthwise, the
                   convolution as three shifted adds), q and k of unit norm
                   per head, q scaled by key_dim^-0.5;
                   beta = 2 sigmoid(b(x)), g = -exp(A_log) softplus(a(x) + dt_bias);
                   S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;
                   o = S^T q   (a `lax.scan` over positions, S zero at first);
                   y = o_proj(rmsnorm_head(o) * silu(g_proj(x)))
    full layer     dense causal softmax over all positions, RMSNorm on the
                   projected q and k, no rotary embedding
    block          h = x + norm(mixer(x));  out = h + norm(mlp(h)), SwiGLU

It reads the program's parameter tree (flax names, HF's projections) upcast
to float32, and nothing else of the program."""

from __future__ import annotations

from typing import Any, Dict

LINEAR = "linear_attention"


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _conv_silu(x, taps):
    """x [S, C], taps [4, C]: y_t = sum_j taps[j] x_{t-3+j}, then SiLU."""
    import jax
    import jax.numpy as jnp

    width = taps.shape[0]
    y = x * taps[width - 1]
    for back in range(1, width):
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:back]), x[:-back]], axis=0)
        y = y + shifted * taps[width - 1 - back]
    return jax.nn.silu(y)


def _unit(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear_mixer(p, x, kw, f32):
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, dk, dv = (kw["linear_num_key_heads"], kw["linear_key_head_dim"],
                 kw["linear_value_head_dim"])
    proj = lambda name: x @ f32(p[name]["kernel"])
    q = _conv_silu(proj("q_proj"), f32(p["conv_q"])).reshape(s, h, dk)
    k = _conv_silu(proj("k_proj"), f32(p["conv_k"])).reshape(s, h, dk)
    v = _conv_silu(proj("v_proj"), f32(p["conv_v"])).reshape(s, h, dv)
    q, k = _unit(q) * dk ** -0.5, _unit(k)
    beta = jax.nn.sigmoid(proj("b_proj"))
    if kw.get("linear_allow_neg_eigval", True):
        beta = 2.0 * beta
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
        proj("a_proj") + f32(p["dt_bias"]))

    def token(state, xs):
        qt, kt, vt, gt, bt = xs  # [H, *]
        state = state * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, f32(p["o_norm"]["scale"]), kw["rms_norm_eps"])
    gate = jax.nn.silu(proj("g_proj")).reshape(s, h, dv)
    return (o * gate).reshape(s, h * dv) @ f32(p["o_proj"]["kernel"])


def _full_mixer(p, x, kw, f32):
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, d = kw["num_heads"], kw["head_dim"]
    eps = kw["rms_norm_eps"]
    q = _rms(x @ f32(p["q_proj"]["kernel"]), f32(p["q_norm"]["scale"]), eps)
    k = _rms(x @ f32(p["k_proj"]["kernel"]), f32(p["k_norm"]["scale"]), eps)
    v = x @ f32(p["v_proj"]["kernel"])
    q, k, v = (t.reshape(s, h, d) for t in (q, k, v))
    pos = jnp.arange(s)
    w = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    w = jax.nn.softmax(
        jnp.where((pos[None, :] <= pos[:, None])[None], w, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", w, v).reshape(s, h * d)
    return o @ f32(p["o_proj"]["kernel"])


def logits(params: Dict[str, Any], ids, kw: Dict[str, Any]):
    """ids [S] -> float32 logits [S, vocab] of one sequence, causal."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps = kw["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"]["embedding"])[ids]
        for i, kind in enumerate(kw["layer_types"]):
            p = params[f"layers_{i}"]
            if kind == LINEAR:
                mixed = _linear_mixer(p["linear_attn"], x, kw, f32)
            else:
                mixed = _full_mixer(p["self_attn"], x, kw, f32)
            x = x + _rms(mixed, f32(p["post_attention_layernorm"]["scale"]),
                         eps)
            m = p["mlp"]
            gate = x @ f32(m["gate_proj"]["kernel"])
            up = x @ f32(m["up_proj"]["kernel"])
            mlp = (jax.nn.silu(gate) * up) @ f32(m["down_proj"]["kernel"])
            x = x + _rms(mlp, f32(p["post_feedforward_layernorm"]["scale"]),
                         eps)
        x = _rms(x, f32(params["norm"]["scale"]), eps)
        return x @ f32(params["lm_head"]["kernel"])


def logprobs(params, ids, kw):
    import jax

    return jax.nn.log_softmax(logits(params, ids, kw), axis=-1)
