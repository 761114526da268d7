"""The plain reference of the Granite 4.0-H block: float32, `jax.numpy` only,
matmuls at `highest` precision, no kernels, no cache, no chunks of the
recurrence, no sorting of tokens by expert, nothing imported from the program.

It follows huggingface.co/ibm-granite/granite-4.0-h-small's config.json and
HF's `modeling_granitemoehybrid.py` (its slow path, the one that states the
mathematics); what the config does not state is under `assumed` in
configs/granite-4.0-h-small-serve.json.

    h0           embedding_multiplier * E[ids]
    layer i      a = x + r * mixer(rmsnorm(x));  u = rmsnorm(a);
                 out = a + r * (moe(u) + shared(u)),  r = residual_multiplier;
                 the mixer is attention where layer_types[i] == "attention",
                 Mamba-2 otherwise
    attention    q of num_heads heads, k and v of num_kv_heads, each query
                 head over the K/V head of its group; dense causal
                 softmax(attention_multiplier * q k^T), no bias, no positional
                 encoding
    mamba-2      (z, xBC, dt) = split(in_proj u);  xBC = silu(conv4(xBC) + b)
                 (causal, depthwise, as three shifted adds) over x, B and C
                 together;  dt = softplus(dt + dt_bias) a head;
                 A = -exp(A_log) a head;
                 S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t;
                 y_t[h] = S_t[h] C_t + D[h] x_t[h]  (a `lax.scan` over
                 positions, S zero at first);  g = y silu(z);
                 g rsqrt(mean(g^2) + eps) w over all of d_inner;  out_proj
    moe          logits = u W_r over all num_experts; the top k; softmax over
                 those k logits;  sum over the chosen experts THIS TREE HOLDS
                 (`experts_held` = first, count: the stacks' rows) of
                 w_e down_e(silu(gate_e u) * up_e u): every held expert over
                 every token, weight 0 where it was not chosen (a scan over
                 the experts, one upcast at a time). What the absent experts
                 would add is left out, as in the program: it is the peer's.
    shared       W_out(silu(w[:, :s]) * w[:, s:]), w = W_in u, every token
    head         rmsnorm, the embedding transposed, over logits_scaling

It reads the program's parameter tree (flax names, HF's projections, the held
experts as stacks `gate_up` [count, H, 2I] and `down` [count, I, H]) upcast
to float32, and nothing else of the program. `logprobs(..., rows=)` runs the
head on some positions only, and `hidden(..., block=)` runs the layers'
position-wise parts (everything but the recurrence and attention's scores) in
blocks of positions, so that two thousand positions fit beside the weights."""

from __future__ import annotations

from typing import Any, Dict, Optional


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


def _mamba2_mixer(p, u, kw, f32):
    import jax
    import jax.numpy as jnp

    heads, width, n = (kw["mamba_n_heads"], kw["mamba_d_head"],
                       kw["mamba_d_state"])
    d = heads * width
    z, xbc, dt = jnp.split(u @ f32(p["in_proj"]["kernel"]),
                           [d, 2 * d + 2 * n], axis=-1)
    xbc = _conv_silu(xbc, f32(p["conv1d_weight"]), f32(p["conv1d_bias"]))
    x, b, c = jnp.split(xbc, [d, d + n], axis=-1)
    x = x.reshape(-1, heads, width)
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))          # [S, heads]
    a = -jnp.exp(f32(p["A_log"]))                         # [heads]

    def token(s, xs):
        xt, dtt, bt, ct = xs  # [heads, P], [heads], [N], [N]
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return s, jnp.sum(s * ct[None, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, n), jnp.float32),
                        (x, dt, b, c))
    y = y + f32(p["D"])[:, None] * x
    g = y.reshape(-1, d) * jax.nn.silu(z)
    g = _rms(g, f32(p["norm"]), kw["rms_norm_eps"])
    return g @ f32(p["out_proj"]["kernel"])


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
        w = jnp.einsum("qrd,kd->rqk", qg, kg) * kw["attention_multiplier"]
        w = jax.nn.softmax(
            jnp.where((pos[None, :] <= pos[:, None])[None], w, -jnp.inf), -1)
        return None, jnp.einsum("rqk,kd->qrd", w, vg)

    _, o = jax.lax.scan(group, None, (q.transpose(1, 0, 2, 3),
                                      k.transpose(1, 0, 2),
                                      v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(s, h * d) @ f32(
        p["o_proj"]["kernel"])


def _moe(p, x, kw, f32):
    """x [T,H] -> [T,H]: each token's top_k experts by plain indexing into a
    [T,E] weight table over all the router's columns, then every expert of
    the stacks (columns first .. first+count-1) over every token."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    first, count = kw["experts_held"]
    logits = x @ f32(p["router"])
    top, chosen = jax.lax.top_k(logits, kw["num_experts_per_tok"])
    table = jnp.zeros_like(logits).at[jnp.arange(t)[:, None], chosen].set(
        jax.nn.softmax(top, axis=-1))
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


def _shared(p, x, kw, f32):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(x @ f32(p["input_linear"]["kernel"]), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ f32(p["output_linear"]["kernel"])


def _feed_forward(p, a, kw, f32):
    u = _rms(a, f32(p["post_attention_layernorm"]["scale"]),
             kw["rms_norm_eps"])
    return (_moe(p["block_sparse_moe"], u, kw, f32)
            + _shared(p["shared_mlp"], u, kw, f32))


def hidden(params: Dict[str, Any], ids, kw: Dict[str, Any],
           block: Optional[int] = None):
    """ids [S] -> the float32 stream [S, hidden] after the last layer and the
    final norm, of one sequence, causal. `block`: the experts' part in blocks
    of that many positions (S a multiple of it)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps, r = kw["rms_norm_eps"], kw["residual_multiplier"]
    x = kw["embedding_multiplier"] * f32(
        params["embed_tokens"]["embedding"])[ids]
    for i, kind in enumerate(kw["layer_types"]):
        p = params[f"layers_{i}"]
        normed = _rms(x, f32(p["input_layernorm"]["scale"]), eps)
        if kind == "attention":
            x = x + r * _attention(p["self_attn"], normed, kw, f32)
        else:
            x = x + r * _mamba2_mixer(p["mamba"], normed, kw, f32)
        if block is None:
            ff = _feed_forward(p, x, kw, f32)
        else:
            ff = jax.lax.map(lambda a: _feed_forward(p, a, kw, f32),
                             x.reshape(-1, block, x.shape[-1])).reshape(
                                 x.shape)
        x = x + r * ff
    return _rms(x, f32(params["norm"]["scale"]), eps)


def logits(params: Dict[str, Any], ids, kw: Dict[str, Any], rows=None,
           block: Optional[int] = None, one: Optional[str] = None):
    """ids [S] -> float32 logits [S, vocab] (of positions `rows` if given).
    `one`: the name of a multiplier to read as 1 (the tests' proof that each
    of the four matters)."""
    import jax
    import jax.numpy as jnp

    if one is not None:
        kw = {**kw, one: 1.0}
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, kw, block)
        if rows is not None:
            x = x[rows]
        return x @ jnp.asarray(params["embed_tokens"]["embedding"],
                               jnp.float32).T / kw["logits_scaling"]


def logprobs(params, ids, kw, rows=None, block=None, one=None):
    import jax

    return jax.nn.log_softmax(logits(params, ids, kw, rows, block, one),
                              axis=-1)
