"""The plain reference of the Nemotron-H block (Nemotron 3 Super): float32,
`jax.numpy` only, matmuls at `highest` precision, no kernels, no cache, no
chunks of the recurrence, no sorting of tokens by expert, nothing imported
from the program.

It follows huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's
config.json (`model_type` nemotron_h); what the config does not state is
under `assumed` in configs/nemotron-3-super-120b-a12b-serve.json.

    h0           E[ids]
    block i      x + mixer_i(rmsnorm(x)), eps 1e-5: ONE mixer a block, of the
                 kind hybrid_override_pattern[i]
    M  mamba-2   (z, xBC, dt) = split(in_proj u);  xBC = silu(conv4(xBC) + b)
                 (causal, depthwise, as three shifted adds) over x and the G
                 groups' B and C together;  dt = softplus(dt + dt_bias) a
                 head;  A = -exp(A_log) a head;  head h reads group
                 g = h // (heads / G):
                 S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h]
                          + dt_t[h] x_t[h] (x) B_t[g];
                 y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]  (a `lax.scan` over
                 positions, S zero at first);  v = y silu(z);
                 v rsqrt(mean(v^2) + eps) w, the mean WITHIN each of the G
                 runs of d_inner / G channels;  out_proj
    *  attention q of num_heads heads, k and v of num_kv_heads, each query
                 head over the K/V head of its group; dense causal
                 softmax(head_dim^-0.5 q k^T), no bias, no positional encoding
    E  experts   s = sigmoid(u W_r) over all num_experts; the top k of
                 s + bias; w_e = scale s_e / sum_chosen s;  l = u W_fc1 (the
                 latent);  r = sum over the chosen experts THIS TREE HOLDS
                 (`experts_held` = first, count: the stacks' rows) of
                 w_e down_e(relu(up_e l)^2): every held expert over every
                 token, weight 0 where it was not chosen (a scan over the
                 experts, one upcast at a time);  r W_fc2 + shared(u),
                 shared(u) = W_down relu(W_up u)^2 on the hidden state, every
                 token. What the absent experts would add is left out, as in
                 the program: it is the peers'.
    head         rmsnorm, lm_head (untied)

It reads the program's parameter tree (flax names, HF's projections, the held
experts as stacks `up` [count, L, I] and `down` [count, I, L]) upcast to
float32, and nothing else of the program. `logprobs(..., rows=)` runs the
head on some positions only."""

from __future__ import annotations

from typing import Any, Dict


# Blocks of columns the head is upcast and multiplied in.
HEAD_BLOCKS = 8


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


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


def _mamba2_mixer(p, u, kw, f32):
    import jax
    import jax.numpy as jnp

    heads, width, n, groups = (kw["mamba_n_heads"], kw["mamba_d_head"],
                               kw["mamba_d_state"], kw["mamba_n_groups"])
    d = heads * width
    z, xbc, dt = jnp.split(u @ f32(p["in_proj"]["kernel"]),
                           [d, 2 * d + 2 * groups * n], axis=-1)
    xbc = _conv_silu(xbc, f32(p["conv1d_weight"]), f32(p["conv1d_bias"]))
    x, b, c = jnp.split(xbc, [d, d + groups * n], axis=-1)
    x = x.reshape(-1, heads, width)
    # a head's own B and C: those of its group, the heads of a group in a row
    of_head = lambda m: jnp.repeat(m.reshape(-1, groups, n), heads // groups,
                                   axis=1)              # [S, heads, N]
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))          # [S, heads]
    a = -jnp.exp(f32(p["A_log"]))                         # [heads]

    def token(s, xs):
        xt, dtt, bt, ct = xs  # [heads, P], [heads], [heads, N], [heads, N]
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, n), jnp.float32),
                        (x, dt, of_head(b), of_head(c)))
    y = y + f32(p["D"])[:, None] * x
    v = (y.reshape(-1, d) * jax.nn.silu(z)).reshape(-1, groups, d // groups)
    v = v * jax.lax.rsqrt(
        jnp.mean(jnp.square(v), axis=-1, keepdims=True) + kw["rms_norm_eps"])
    return (v.reshape(-1, d) * f32(p["norm"])) @ f32(p["out_proj"]["kernel"])


def _attention(p, x, kw, f32):
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    q = (x @ f32(p["q_proj"]["kernel"])).reshape(s, hk, h // hk, d)
    k = (x @ f32(p["k_proj"]["kernel"])).reshape(s, hk, d)
    v = (x @ f32(p["v_proj"]["kernel"])).reshape(s, hk, d)
    pos = jnp.arange(s)

    def group(_, qkv):  # one K/V head and its query heads at a time
        qg, kg, vg = qkv  # [S, rep, D], [S, D], [S, D]
        w = jnp.einsum("qrd,kd->rqk", qg, kg) * d ** -0.5
        w = jax.nn.softmax(
            jnp.where((pos[None, :] <= pos[:, None])[None], w, -jnp.inf), -1)
        return None, jnp.einsum("rqk,kd->qrd", w, vg)

    _, o = jax.lax.scan(group, None, (q.transpose(1, 0, 2, 3),
                                      k.transpose(1, 0, 2),
                                      v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(s, h * d) @ f32(
        p["o_proj"]["kernel"])


def _routed(p, u, latent, kw, f32):
    """u [T,H], latent [T,L] -> [T,L]: each token's top_k experts by plain
    indexing into a [T,E] weight table over all the router's columns, then
    every expert of the stacks (columns first .. first+count-1) over every
    token's latent."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    first, count = kw["experts_held"]
    scores = jax.nn.sigmoid(u @ f32(p["router"]))
    _, chosen = jax.lax.top_k(scores + f32(p["bias"]),
                              kw["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    table = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], chosen].set(
        kw["routed_scaling_factor"] * picked
        / jnp.sum(picked, axis=-1, keepdims=True))

    def expert(y, e):
        up, down, w = e
        return y + w[:, None] * (_relu2(latent @ f32(up)) @ f32(down)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(latent),
                        (p["up"], p["down"], table.T[first:first + count]))
    return y


def _experts(p, u, kw, f32):
    latent = u @ f32(p["fc1_latent_proj"]["kernel"])
    routed = _routed(p["experts"], u, latent, kw, f32)
    shared = _relu2(u @ f32(p["shared_experts"]["up_proj"]["kernel"])) @ f32(
        p["shared_experts"]["down_proj"]["kernel"])
    return routed @ f32(p["fc2_latent_proj"]["kernel"]) + shared


_MIXERS = {"M": _mamba2_mixer, "*": _attention, "E": _experts}


def hidden(params: Dict[str, Any], ids, kw: Dict[str, Any]):
    """ids [S] -> the float32 stream [S, hidden] after the last block and the
    final norm, of one sequence, causal."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps = kw["rms_norm_eps"]
    # (the rows first, then the upcast: the same numbers, and the table's
    # 131,072 rows are never whole in float32 beside the weights)
    x = f32(params["embed_tokens"]["embedding"][ids])
    for i, kind in enumerate(kw["hybrid_override_pattern"]):
        p = params[f"layers_{i}"]
        x = x + _MIXERS[kind](p["mixer"],
                              _rms(x, f32(p["norm"]["scale"]), eps), kw, f32)
    return _rms(x, f32(params["norm_f"]["scale"]), eps)


def logits(params: Dict[str, Any], ids, kw: Dict[str, Any], rows=None):
    """ids [S] -> float32 logits [S, vocab] (of positions `rows` if given)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, kw)
        if rows is not None:
            x = x[rows]
        # (the head a block of columns at a time, for the same reason)
        head = params["lm_head"]["kernel"]
        step = max(1, head.shape[1] // HEAD_BLOCKS)
        return jnp.concatenate(
            [x @ jnp.asarray(head[:, at:at + step], jnp.float32)
             for at in range(0, head.shape[1], step)], axis=-1)


def logprobs(params, ids, kw, rows=None):
    import jax

    return jax.nn.log_softmax(logits(params, ids, kw, rows), axis=-1)
