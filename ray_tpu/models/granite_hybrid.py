"""Granite 4.0-H (`granitemoehybrid`): a decoder whose layers are of two
kinds, a Mamba-2 (SSD) mixer on most and grouped-query attention without
positions on the rest (`layer_types`), each followed by a top-k mixture of
experts beside a shared expert; four scalar multipliers on the embedding,
the attention scores, both residual branches and the logits.

Follows huggingface.co/ibm-granite/granite-4.0-h-small's config.json and HF's
`modeling_granitemoehybrid.py`; parameter names are HF's, the experts of a
layer held as two stacks. What the config does not state is listed under
`assumed` in benchmark/configs/granite-4.0-h-small-serve.json.

    h0          embedding_multiplier * E[ids]
    block       a = x + r * mixer(RMSNorm(x));  u = RMSNorm(a);
                out = a + r * (moe(u) + shared(u)),  r = residual_multiplier
    attention   num_heads query heads over num_kv_heads K/V heads, no bias,
                no positional encoding, softmax(attention_multiplier q k^T)
    mamba       (z, xBC, dt) = in_proj(u); xBC = silu(conv4(xBC) + b), the
                convolution over x, B and C together; dt = softplus(dt +
                dt_bias) a head; the recurrence of ops/ssm.py (`ssd_*`) with
                A = -exp(A_log) a head; g = y silu(z); g rsqrt(mean(g^2) +
                eps) w over all of d_inner; out_proj
    experts     ops/moe.py over all `num_experts` columns of the router,
                softmax over the top k; `experts_held = (first, count)`: this
                chip holds those experts' weights and computes their part of
                the sum (expert parallelism's share; the rest is the peer's)
    shared      output_linear(silu(w[:, :s]) * w[:, s:]), w = input_linear(u)
    head        the embedding transposed, over logits_scaling, on a prefill's
                last position only (`num_logits_to_keep`)

Serving cache, per layer (`init_cache`): an attention layer holds paged K/V
like `LlamaModel`; a Mamba layer holds, per engine slot, the last three
inputs of its convolution [3, d_inner + 2 N] and the float32 state
[heads, d_head, N], the N states on the lanes. A state row is the slot's
index: prefill overwrites the rows it is given from zero, decode updates
every active row in place.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.initializers import embed_init, kernel_init
from ray_tpu.models.jamba import _conv_init, _dt_bias_init
from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.sdar_moe import _stack_init
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.moe import moe_layer
from ray_tpu.ops.paged_attention import (init_kv_pages, paged_attention,
                                         paged_write)
from ray_tpu.ops.ssm import causal_conv, ssd_scan, ssd_scan_plain, ssd_step

MAMBA, ATTENTION = "mamba", "attention"

# Standard deviation of a seeded router's logits (its input has unit RMS),
# sharpened as models/sdar_moe.py sharpens its own and for its reason: at 1
# (lecun normal) the softmax over the top 10 of 72 logits is nearly even, 0.25
# down to 0.05, so a near-tie at the tenth place, which bf16 activations and a
# float32 reference decide differently now and then, swaps a twentieth of the
# layer's output (or drops it, where one of the two is the peer's); at 4 the
# weights fall from about 0.7 to 0.005. Not SDAR's 3: on the chip no limit
# stood with room between sound runs (to 0.0071 over 85 seeds) and runs in
# four significant bits (from 0.0106) there; at 4 they read to 0.0049 and
# from 0.0122 (PERF.md section 6, PR 42).
ROUTER_LOGIT_STD = 4.0


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100_352
    hidden_size: int = 4096
    intermediate_size: int = 768          # one routed expert's width
    shared_intermediate_size: int = 1536
    num_experts: int = 72                 # the router's columns
    num_experts_per_tok: int = 10
    experts_held: Tuple[int, int] = (0, 72)   # (first, count) on this chip
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,)
                                    + (MAMBA,) * 4) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    max_seq_len: int = 131_072
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # (a list from a JSON file hashes as a tuple does)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B, then C (one
        group)."""
        return self.d_inner + 2 * self.mamba_d_state

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "GraniteHybridConfig":
        """Test-sized: three layers, 8 experts top-3, float32, seconds on
        the CPU."""
        return GraniteHybridConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=16,
            shared_intermediate_size=32, num_experts=8,
            num_experts_per_tok=3, experts_held=(0, 8),
            layer_types=(MAMBA, ATTENTION, MAMBA), num_heads=4,
            num_kv_heads=2, head_dim=16, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, mamba_chunk_size=16, attention_multiplier=0.125,
            max_seq_len=512, dtype=jnp.float32, param_dtype=jnp.float32),
            **kw})


def _a_log_init(key, shape, dtype):
    """Mamba-2: A = -(1 ... heads), one a head, held as log(-A)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(
        dtype)


def _dense(cfg: GraniteHybridConfig, features: int,
           name: Optional[str]) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=kernel_init,
                    name=name)


def _norm(cfg: GraniteHybridConfig, name: Optional[str]) -> nn.Module:
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)


def _embed(cfg: GraniteHybridConfig, name: Optional[str]) -> nn.Embed:
    return nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, embedding_init=embed_init,
                    name=name)


class Mamba2Mixer(nn.Module):
    """`state` is None (no cache: the recurrence token by token over the
    whole sequence) or the layer's (conv_tail, S) pool, with `rows` = the pool
    rows a prefill overwrites, or None for decode (one token for every row of
    the pool)."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, mask=None, state=None, rows=None):
        cfg = self.cfg
        b, s, _ = u.shape
        d, n, heads, width = (cfg.d_inner, cfg.mamba_d_state,
                              cfg.mamba_n_heads, cfg.mamba_d_conv)
        f32 = lambda t: t.astype(jnp.float32)
        if mask is None:
            mask = jnp.ones((b, s), bool)
        z, xbc, dt = jnp.split(
            _dense(cfg, 2 * d + 2 * n + heads, "in_proj")(u),
            [d, d + cfg.conv_dim], axis=-1)
        taps = self.param("conv1d_weight", _conv_init, (width, cfg.conv_dim),
                          cfg.param_dtype)
        bias = self.param("conv1d_bias", _conv_init, (cfg.conv_dim,),
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
        x, bm, cm = jnp.split(xbc, [d, d + n], axis=-1)
        x = x.reshape(b, s, heads, cfg.mamba_d_head)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        dt = jnp.where(mask[:, :, None],
                       jax.nn.softplus(f32(dt) + dt_bias), 0.0)
        a = -jnp.exp(f32(self.param("A_log", _a_log_init, (heads,),
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
        # The gated norm, over all of d_inner (one group).
        g = f32(y).reshape(b, s, d) * jax.nn.silu(f32(z))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        g = g * f32(self.param("norm", nn.initializers.ones, (d,),
                               jnp.float32))
        return _dense(cfg, cfg.hidden_size, "out_proj")(
            g.astype(cfg.dtype)), new_state


class Attention(nn.Module):
    """Causal softmax attention, `num_heads` query heads over `num_kv_heads`
    K/V heads, no bias, no positional encoding, the scores times
    `attention_multiplier`."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None):
        """`kv_pages`: this layer's (k_pages, v_pages) when serving, with
        `paged` = (page_table, write_mask, seq_lens); None for the whole
        sequence without a cache."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        scale = cfg.attention_multiplier
        q = _dense(cfg, h * d, "q_proj")(x).reshape(b, s, h, d)
        k = _dense(cfg, hk * d, "k_proj")(x).reshape(b, s, hk, d)
        v = _dense(cfg, hk * d, "v_proj")(x).reshape(b, s, hk, d)
        if kv_pages is None:
            out = attention_reference(q, k, v, causal=True, scale=scale)
        else:
            page_table, write_mask, seq_lens = paged
            k_pages, v_pages = (
                paged_write(pages, new, page_table, positions, write_mask)
                for pages, new in zip(kv_pages, (k, v)))
            kv_pages = (k_pages, v_pages)
            attend = lambda q, table, pos, lens: paged_attention(
                q, k_pages, v_pages, table, pos, lens, scale=scale)
            if s == 1:
                out = attend(q, page_table, positions, seq_lens)
            else:
                # A prefill's rows one at a time: the float32 scores of a
                # wave of 8 x 2,048 queries over 2,304 keys and 32 heads
                # would be 4.5 GiB beside the weights.
                out = jax.lax.map(
                    lambda row: attend(*(t[None] for t in row))[0],
                    (q, page_table, positions, seq_lens))
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages


class SparseMoe(nn.Module):
    """The routed experts this chip holds (`ops/moe.py`): the router's kernel
    float32 over all `num_experts`, the held experts two stacks in the
    compute dtype."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hid, inter = cfg.hidden_size, cfg.intermediate_size
        first, count = cfg.experts_held
        router = self.param("router", nn.initializers.variance_scaling(
            ROUTER_LOGIT_STD ** 2, "fan_in", "truncated_normal"),
            (hid, cfg.num_experts), jnp.float32)
        gate_up = self.param("gate_up", _stack_init, (count, hid, 2 * inter),
                             cfg.param_dtype)
        down = self.param("down", _stack_init, (count, inter, hid),
                          cfg.param_dtype)
        b, s, _ = x.shape
        y, load = moe_layer(
            x.reshape(b * s, hid), router, gate_up.astype(cfg.dtype),
            down.astype(cfg.dtype), cfg.num_experts_per_tok,
            held=None if count == cfg.num_experts else (first, count))
        # `ops.moe.Load` of this call, for whoever asks for the collection
        # (the engine's programs).
        self.sow("expert_load", "load", jnp.stack(load))
        return y.reshape(b, s, hid)


class SharedMlp(nn.Module):
    """The shared expert: a SwiGLU every token goes through whole."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate, up = jnp.split(_dense(cfg, 2 * cfg.shared_intermediate_size,
                                    "input_linear")(x), 2, axis=-1)
        return _dense(cfg, cfg.hidden_size, "output_linear")(
            nn.silu(gate) * up)


class GraniteHybridLayer(nn.Module):
    """A pre-norm block: the mixer, then the routed experts beside the
    shared one, each branch times `residual_multiplier`."""
    cfg: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, mask, cache, paged, rows):
        cfg = self.cfg
        r = cfg.residual_multiplier
        normed = _norm(cfg, "input_layernorm")(x)
        if self.kind == MAMBA:
            mixed, new_cache = Mamba2Mixer(cfg, name="mamba")(
                normed, mask, cache, rows)
        else:
            mixed, new_cache = Attention(cfg, name="self_attn")(
                normed, positions, cache, paged)
        x = x + (r * mixed).astype(cfg.dtype)
        u = _norm(cfg, "post_attention_layernorm")(x)
        ff = (SparseMoe(cfg, name="block_sparse_moe")(u)
              + SharedMlp(cfg, name="shared_mlp")(u))
        return x + (r * ff).astype(cfg.dtype), new_cache


class GraniteHybridModel(nn.Module):
    cfg: GraniteHybridConfig

    # What the engine reads off a model (as `state_layer_ids`): a prefill
    # wants the head on this many of a row's last positions, not on all (the
    # logits of a wave's 16,384 positions over 100,352 ids would be 6.6 GB).
    num_logits_to_keep = 1

    @property
    def state_layer_ids(self) -> Tuple[int, ...]:
        """Layers whose cache entry is a state per slot, not K/V pages."""
        return tuple(i for i, kind in enumerate(self.cfg.layer_types)
                     if kind == MAMBA)

    @property
    def expert_layer_ids(self) -> Tuple[int, ...]:
        """Layers that sow an `expert_load` (`ops.moe.Load`) a forward, for
        the engine's token-at-a-time programs to sum and report: all."""
        return tuple(range(self.cfg.num_layers))

    def init_cache(self, cache_cfg, mesh=None):
        """Per layer: (k_pages, v_pages) on an attention layer; (conv_tail
        [max_seqs, 3, d_inner + 2 N], S [max_seqs, heads, d_head, N] float32)
        on a Mamba layer, a row per engine slot, the states minor."""
        if mesh is not None:
            raise NotImplementedError(
                "GraniteHybridModel: state layers and expert stacks have no "
                "sharding under a mesh (tensor parallelism is not built for "
                "this family)")
        cfg = self.cfg
        tail = (cache_cfg.max_seqs, cfg.mamba_d_conv - 1, cfg.conv_dim)
        state = (cache_cfg.max_seqs, cfg.mamba_n_heads, cfg.mamba_d_head,
                 cfg.mamba_d_state)
        return [(jnp.zeros(tail, cfg.dtype), jnp.zeros(state, jnp.float32))
                if kind == MAMBA else
                init_kv_pages(cache_cfg, cfg.num_kv_heads, cfg.head_dim,
                              cfg.dtype)
                for kind in cfg.layer_types]

    @nn.nowrap
    def init_params(self, rng):
        """The tree `self.init(rng, ids)["params"]` holds, made layer by
        layer: one compiled initializer per kind of layer, run once for each
        layer of the kind (a constructor has 60 s, and the TPU compiler's
        time for one program over every layer grows with the depth:
        models/olmo_hybrid.py)."""
        cfg = self.cfg
        ids = jnp.zeros((1, 8), jnp.int32)
        x = jnp.zeros((1, 8, cfg.hidden_size), cfg.dtype)

        def of(module, *args):
            return jax.jit(lambda key: module.init(key, *args)["params"])

        layer = {kind: of(GraniteHybridLayer(cfg, kind), x, ids, None, None,
                          None, None) for kind in set(cfg.layer_types)}
        keys = jax.random.split(rng, cfg.num_layers + 2)
        params = {f"layers_{i}": layer[kind](keys[i])
                  for i, kind in enumerate(cfg.layer_types)}
        params["embed_tokens"] = of(_embed(cfg, None), ids)(keys[-2])
        params["norm"] = of(_norm(cfg, None), x)(keys[-1])
        return params

    @nn.compact
    def __call__(self, input_ids, positions=None, paged_kv=None,
                 page_table=None, write_mask=None, seq_lens=None, lora=None,
                 lora_idx=None, slots=None, logits_at=None):
        """The engine's `apply` surface (`LlamaModel`'s). `paged_kv` is the
        list `init_cache` made; `slots` [nb] are the pool rows a prefill
        writes (state from zero), None when decoding one token for every row.
        `logits_at` [B]: the one position of each row the final norm and the
        head run on (logits [B, 1, V]); None: every position. Without
        `paged_kv`: the whole sequence, no cache."""
        cfg = self.cfg
        if lora is not None:
            raise NotImplementedError("GraniteHybridModel has no LoRA banks")
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.arange(s)
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None, :], (b, s))
        embed = _embed(cfg, "embed_tokens")
        x = (cfg.embedding_multiplier * embed(input_ids)).astype(cfg.dtype)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i, kind in enumerate(cfg.layer_types):
            cache = paged_kv[i] if paged_kv is not None else None
            x, new_cache = GraniteHybridLayer(cfg, kind, name=f"layers_{i}")(
                x, positions, write_mask, cache, paged, slots)
            new_caches.append(new_cache)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = embed.attend(_norm(cfg, "norm")(x)) / cfg.logits_scaling
        if paged_kv is not None:
            return logits, new_caches
        return logits
