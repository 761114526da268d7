"""What more than one model family uses, under public names: the RMSNorm and
the rotary embedding, the seeded projection, norm and embedding of a family
whose checkpoint is bf16, the SwiGLU MLP, the routed experts, the Mamba-2
mixer, the draws two families share, and `Decoder`, the base of every
family's model class: what the serving engine reads off a model, each name
with the value of a family that does not say otherwise, the seeded
initializer and the cache a slot indexes.

A family's file (`models/<family>.py`) states its architecture with these and
with `ray_tpu/ops/`; it imports no other family's file, and this module
imports none (tests/test_layering.py). Attention stays in the family files:
the families differ there in what a reader must see.
"""

from __future__ import annotations

import math
from typing import Any, ClassVar, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.initializers import embed_init, kernel_init
from ray_tpu.ops.moe import moe_layer
from ray_tpu.ops.paged_attention import (init_index_pages, init_kv_pages,
                                         init_latent_pages, init_ring_pages)
from ray_tpu.ops.ssm import causal_conv, ssd_scan, ssd_scan_plain, ssd_step


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (x32 * scale.astype(jnp.float32)).astype(self.dtype)


def rope_freqs(head_dim: int, theta: float,
               yarn: Optional[Tuple[float, int, float, float]] = None
               ) -> jax.Array:
    """The rotary inverse frequencies of one kind of layer, [head_dim / 2].
    `yarn` = (factor, original_max_position_embeddings, beta_fast, beta_slow)
    gives YaRN's: pair j keeps its frequency below the dimension that turns
    `beta_fast` times over the original context, takes it over `factor` above
    the one that turns `beta_slow` times, and a linear ramp between (the
    range's ends rounded outwards: HF's `truncate` default)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if yarn is None:
        return freqs
    factor, original, beta_fast, beta_slow = yarn
    turns_at = lambda turns: (head_dim * math.log(
        original / (turns * 2 * math.pi))) / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               factor: float = 1.0) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] or [S]. `freqs` [D/2]: a layer
    kind's own inverse frequencies in place of `rope_freqs(D, theta)`;
    `factor` multiplies cos and sin (YaRN's `attention_factor`)."""
    if freqs is None:
        freqs = rope_freqs(x.shape[-1], theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# The seeded modules of a family whose checkpoint is bf16. `cfg` is any
# family's config: they read `dtype`, `param_dtype`, `rms_norm_eps`,
# `vocab_size` and `hidden_size` off it.

def dense(cfg: Any, features: int, name: Optional[str]) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=kernel_init,
                    name=name)


def norm(cfg: Any, name: Optional[str]) -> nn.Module:
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)


def embed(cfg: Any, name: Optional[str]) -> nn.Embed:
    return nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, embedding_init=embed_init,
                    name=name)


def stack_init(key, shape, dtype, std: float = 1.0):
    """An expert stack [E, fan_in, features]: each expert's kernel as
    `kernel_init` draws a projection (float32, rounded, in blocks), at `std`
    times its deviation."""
    e, fan_in, features = shape
    return kernel_init(key, (e * fan_in, features), dtype,
                       fan_in if std == 1.0 else fan_in / std ** 2
                       ).reshape(shape)


def dt_bias_init(key, shape, dtype):
    """GatedDeltaNet's and Mamba's: a step log-uniform in [1e-3, 0.1], held
    through the inverse of softplus."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def conv_init(key, shape, dtype):
    """torch's Conv1d default for a Mamba mixer's depthwise kernel (and its
    bias) of width 4: uniform in +-1/sqrt(4)."""
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5).astype(dtype)


class Mlp(nn.Module):
    """SwiGLU: down(silu(gate x) * up x), `cfg.intermediate_size` wide."""
    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = dense(cfg, cfg.intermediate_size, "gate_proj")(x)
        up = dense(cfg, cfg.intermediate_size, "up_proj")(x)
        return dense(cfg, cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


# Standard deviation of a seeded router's bias on the choice (sigmoid scores
# lie in (0, 1); the eighth and ninth largest of 128 lie some 0.01 apart).
ROUTER_BIAS_STD = 0.02


class SparseMoe(nn.Module):
    """A layer's routed experts (`ops/moe.py`): the router's kernel float32
    over all `num_experts`, seeded with logits of standard deviation
    `router_std` (its input has unit RMS; each family's value and why is in
    its file), the experts two stacks in the compute dtype, each
    `intermediate` wide. `held = (first, count)`: this chip holds those
    experts' stacks and computes their part of the sum; None: all. `cfg`
    gives `dtype`, `param_dtype` and `hidden_size`. `scoring` "sigmoid"
    (`ops.moe.route`): the layer also holds the float32 `bias` [num_experts]
    that takes part in the choice alone, drawn small and not zero so that a
    seeded model's choosing and weighing differ; the chosen weights sum to
    `scale`. `down_std`: the seeded `down` stacks' deviation, in lecun's.
    `act` "relu2": an expert is down(relu(up r)^2), its first stack `up`
    [count, input, intermediate] where a gated one's ("swiglu") is
    `gate_up`. `rows`, where the caller hands them in beside x: the rows the
    experts take and give back (a latent of each token, its width the
    stacks' input) while the router reads x."""
    cfg: Any
    num_experts: int
    intermediate: int
    top_k: int
    router_std: float
    held: Optional[Tuple[int, int]] = None
    scoring: str = "softmax"
    scale: float = 1.0
    down_std: float = 1.0
    act: str = "swiglu"

    @nn.compact
    def __call__(self, x, rows=None):
        cfg = self.cfg
        hid, inter = cfg.hidden_size, self.intermediate
        wide = hid if rows is None else rows.shape[-1]
        count = self.num_experts if self.held is None else self.held[1]
        router = self.param("router", nn.initializers.variance_scaling(
            self.router_std ** 2, "fan_in", "truncated_normal"),
            (hid, self.num_experts), jnp.float32)
        up = (self.param("gate_up", stack_init, (count, wide, 2 * inter),
                         cfg.param_dtype) if self.act == "swiglu" else
              self.param("up", stack_init, (count, wide, inter),
                         cfg.param_dtype))
        down = self.param("down", stack_init, (count, inter, wide),
                          cfg.param_dtype, self.down_std)
        routing = {}
        if self.scoring != "softmax":
            routing = dict(scoring=self.scoring, scale=self.scale,
                           bias=self.param(
                               "bias", nn.initializers.normal(ROUTER_BIAS_STD),
                               (self.num_experts,), jnp.float32))
        b, s, _ = x.shape
        y, load = moe_layer(
            x.reshape(b * s, hid), router, up.astype(cfg.dtype),
            down.astype(cfg.dtype), self.top_k, held=self.held,
            act=self.act,
            rows=None if rows is None else rows.reshape(b * s, wide),
            **routing)
        # `ops.moe.Load` of this call, for whoever asks for the collection
        # (the engine's programs).
        self.sow("expert_load", "load", jnp.stack(load))
        return y.reshape(b, s, wide)


def a_log_init(key, shape, dtype):
    """Mamba-2: A = -(1 ... heads), one a head, held as log(-A)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(
        dtype)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 (SSD) mixer of a family whose config says `hidden_size`,
    `mamba_n_heads`, `mamba_d_head`, `mamba_d_state`, `mamba_d_conv`,
    `mamba_chunk_size` and `rms_norm_eps`:

        (z, xBC, dt) = in_proj(u); xBC = silu(conv(xBC) + b), the
        convolution over x and the `groups` B and C together; dt =
        softplus(dt + dt_bias) a head; the recurrence of ops/ssm.py
        (`ssd_*`, head h on group h // (heads / groups)) with A = -exp(A_log)
        a head; g = y silu(z); g rsqrt(mean(g^2) + eps) w within each of
        the `groups` runs of d_inner / groups channels; out_proj

    `state` is None (no cache: the recurrence token by token over the
    whole sequence) or the layer's (conv_tail, S) pool, with `rows` = the pool
    rows a prefill overwrites, or None for decode (one token for every row of
    the pool)."""
    cfg: Any
    groups: int = 1

    @nn.compact
    def __call__(self, u, mask=None, state=None, rows=None):
        cfg, groups = self.cfg, self.groups
        b, s, _ = u.shape
        n, heads, width = (cfg.mamba_d_state, cfg.mamba_n_heads,
                           cfg.mamba_d_conv)
        d = heads * cfg.mamba_d_head
        conv_dim = d + 2 * groups * n
        f32 = lambda t: t.astype(jnp.float32)
        if mask is None:
            mask = jnp.ones((b, s), bool)
        z, xbc, dt = jnp.split(
            dense(cfg, d + conv_dim + heads, "in_proj")(u),
            [d, d + conv_dim], axis=-1)
        taps = self.param("conv1d_weight", conv_init, (width, conv_dim),
                          cfg.param_dtype)
        bias = self.param("conv1d_bias", conv_init, (conv_dim,),
                          cfg.param_dtype)
        decode = state is not None and rows is None
        conv, window = causal_conv(xbc, taps, bias,
                                   state[0] if decode else None)
        if decode:
            tail = jnp.where(mask[:, :, None], window[:, 1:], state[0])
        else:
            # The last width-1 inputs before position true_len.
            true_len = jnp.sum(mask, axis=-1)
            tail = jnp.take_along_axis(
                window, (true_len[:, None] + jnp.arange(width - 1))[..., None],
                axis=1)
        # Padding is zero from here on: it changes no state (dt = 0 below),
        # and what a skipped chunk of the scan leaves there is never read.
        xbc = jnp.where(mask[:, :, None], jax.nn.silu(conv), 0.0).astype(
            cfg.dtype)
        x, bm, cm = jnp.split(xbc, [d, d + groups * n], axis=-1)
        x = x.reshape(b, s, heads, cfg.mamba_d_head)
        bm, cm = (m.reshape(b, s, groups, n) for m in (bm, cm))
        dt_bias = self.param("dt_bias", dt_bias_init, (heads,), jnp.float32)
        dt = jnp.where(mask[:, :, None],
                       jax.nn.softplus(f32(dt) + dt_bias), 0.0)
        a = -jnp.exp(f32(self.param("A_log", a_log_init, (heads,),
                                    jnp.float32)))
        skip = f32(self.param("D", nn.initializers.ones, (heads,),
                              jnp.float32))
        new_state = None
        if decode:
            y, pool = ssd_step(x[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], a, skip,
                               state[1], mask[:, 0])
            y, new_state = y[:, None], (tail, pool)
        elif state is None:
            y, _ = ssd_scan_plain(x, dt, bm, cm, a, skip)
        else:
            y, last = ssd_scan(x, dt, bm, cm, a, skip, true_len,
                               chunk=cfg.mamba_chunk_size)
            new_state = (state[0].at[rows].set(tail.astype(state[0].dtype)),
                         state[1].at[rows].set(last))
        # The gated norm, within each group's channels.
        g = (f32(y).reshape(b, s, d) * jax.nn.silu(f32(z))).reshape(
            b, s, groups, d // groups)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        g = g.reshape(b, s, d) * f32(self.param(
            "norm", nn.initializers.ones, (d,), jnp.float32))
        return dense(cfg, cfg.hidden_size, "out_proj")(
            g.astype(cfg.dtype)), new_state


def batch_positions(input_ids, positions):
    """`positions` as the layers take them, [B, S]: of a whole sequence
    from 0 where none are given."""
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.arange(s)
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (b, s))
    return positions


def no_lora(model, lora) -> None:
    if lora is not None:
        raise NotImplementedError(
            f"{type(model).__name__} has no LoRA banks")


def init_params(rng, cfg, layers: Sequence[nn.Module],
                tail: Dict[str, nn.Module], length: int = 8):
    """The tree `model.init(rng, ids)["params"]` holds, made group by group:
    `layers_<i>` from `layers[i]`, `embed_tokens`, then `tail`'s modules
    under their names (the final norm, the head where it is not the
    embedding's). One compiled initializer per distinct module, run once for
    each layer it makes: one program over all 16 layers took the TPU's
    compiler 88 s on the chip's host (my chip run, PR 29), longer than an
    actor's constructor may take. Layer i draws from key i of
    `num_layers + 1 + len(tail)`, the others from the last ones in order: a
    seed's tree is the same whoever is asked for it. Traced on `length`
    positions."""
    ids = jnp.zeros((1, length), jnp.int32)
    x = jnp.zeros((1, length, cfg.hidden_size), cfg.dtype)

    def of(module, *args):
        return jax.jit(lambda key: module.init(key, *args)["params"])

    layer = {module: of(module, x, ids) for module in set(layers)}
    keys = jax.random.split(rng, len(layers) + 1 + len(tail))
    params = {f"layers_{i}": layer[module](keys[i])
              for i, module in enumerate(layers)}
    params["embed_tokens"] = of(embed(cfg, None), ids)(keys[len(layers)])
    for key, (name, module) in zip(keys[len(layers) + 1:], tail.items()):
        params[name] = of(module, x)(key)
    return params


class Decoder(nn.Module):
    """The base of a family's model class (`cfg`: its config). The class
    attributes are what the serving engine reads off a model
    (llm/_internal/engine.py), each with the value of a family that does not
    say otherwise; a family overrides one with a value or a property."""

    # Layers whose cache entry is a state per engine slot (prefill overwrites
    # the rows it is given from zero, decode updates every row in place).
    state_layer_ids: ClassVar[Tuple[int, ...]] = ()
    # Layers whose cache entry is a ring of pages a slot over the last
    # `sliding_window` keys, not pages from the allocator.
    ring_layer_ids: ClassVar[Tuple[int, ...]] = ()
    sliding_window: ClassVar[int] = 0
    # Layers whose cache entry is one pool of the allocator's pages, a
    # token's row `latent_width` values for all heads (latent attention).
    latent_layer_ids: ClassVar[Tuple[int, ...]] = ()
    latent_width: ClassVar[int] = 0
    # Layers that keep nothing between steps (an expert layer alone, an MLP
    # alone): their cache entry is empty, `()`, in and out of both programs.
    cacheless_layer_ids: ClassVar[Tuple[int, ...]] = ()
    # Layers whose (k_pages, v_pages) have an index pool beside them: a
    # page's `index_segments` segment means, by which a query chooses the
    # pages it attends to (learned sparse attention). Each sows a
    # `page_load` (pages selected, pages visible) a decode step.
    index_layer_ids: ClassVar[Tuple[int, ...]] = ()
    index_segments: ClassVar[int] = 4
    # Layers that sow an `expert_load` (`ops.moe.Load`) a forward, for the
    # engine's token-at-a-time programs to sum and report.
    expert_layer_ids: ClassVar[Tuple[int, ...]] = ()
    # Positions a decode step makes for a row. Above 1 the model generates
    # by diffusion over blocks and says `denoising_steps`, `remasking` and
    # `mask_token_id` too (models/sdar_moe.py).
    block_length: ClassVar[int] = 1
    # 1: a prefill wants the final norm and the head on a row's last
    # position only, and `__call__` takes `logits_at`.
    num_logits_to_keep: ClassVar[int] = 0

    def init_cache(self, cache_cfg, mesh=None, tail=None, state=None):
        """The serving engine's cache of a family without sharding rules, an
        entry a layer: on a state layer (zeros [max_seqs, *tail] in the
        compute dtype, zeros [max_seqs, *state] float32), a row per engine
        slot, the state alone where the family gives no `tail`; on a ring
        layer `max_seqs` rings of pages; on a latent layer one pool of rows;
        nothing, `()`, on a cacheless layer; (k_pages, v_pages) from the
        allocator's pool on the others, and on an index layer the pool of
        segment means as the third."""
        if mesh is not None:
            raise NotImplementedError(
                f"{type(self).__name__}: neither its parameters nor its "
                "layers' caches have a sharding under a mesh (tensor "
                "parallelism is not built for this family)")
        cfg, n = self.cfg, cache_cfg.max_seqs
        states, rings = self.state_layer_ids, self.ring_layer_ids

        def entry(i):
            if i in states:
                held = jnp.zeros((n, *state), jnp.float32)
                return held if tail is None else (
                    jnp.zeros((n, *tail), cfg.dtype), held)
            if i in self.latent_layer_ids:
                return init_latent_pages(cache_cfg, self.latent_width,
                                         cfg.dtype)
            if i in self.cacheless_layer_ids:
                return ()
            kv = cfg.num_kv_heads, cfg.head_dim, cfg.dtype
            if i in rings:
                return init_ring_pages(cache_cfg, self.sliding_window, *kv)
            if i in self.index_layer_ids:
                return (*init_kv_pages(cache_cfg, *kv), init_index_pages(
                    cache_cfg, self.index_segments,
                    cfg.num_kv_heads * cfg.head_dim))
            return init_kv_pages(cache_cfg, *kv)

        return [entry(i) for i in range(cfg.num_layers)]
