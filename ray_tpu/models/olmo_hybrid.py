"""Olmo-Hybrid: a decoder whose layers are of two kinds (`layer_types`):
gated-delta linear attention (arXiv:2412.06464), which remembers a sequence in
a fixed state per head, and full softmax attention on every fourth layer.

Follows huggingface.co/allenai/Olmo-Hybrid-7B's config.json; parameter names
are HF's. What that config does not state follows the family's conventions and
is listed under `assumed` in benchmark/configs/olmo-hybrid-7b-serve.json:
post-norm blocks and a q/k RMSNorm (Olmo 2/3), no rotary embedding
(`rope_theta` null), no convolution bias, GatedDeltaNet's ranges for `A_log`
and `dt_bias`.

Serving cache, per layer (`init_cache`): a full layer holds paged K/V like
`LlamaModel`; a linear layer holds, per engine slot, the last three inputs of
its convolutions and the float32 state [heads, key_dim, value_dim], laid out
as `ops/linear_attention.py` `state_shape` says. A state row is the slot's
index: nothing is allocated, prefill overwrites the rows it is given from
zero, decode updates every active row in place.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (Decoder, Mlp, RMSNorm, batch_positions,
                                   dense, dt_bias_init, embed, init_params,
                                   no_lora, norm)
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.linear_attention import (gdn_chunked, gdn_decode, pack,
                                          state_shape)
from ray_tpu.ops.paged_attention import paged_write_attend

LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100_352
    hidden_size: int = 3840
    intermediate_size: int = 11_008
    layer_types: Tuple[str, ...] = PERIOD * 8
    num_heads: int = 30
    num_kv_heads: int = 30
    head_dim: int = 128
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_seq_len: int = 65_536
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_kv_heads != self.num_heads:
            raise ValueError("full layers have one KV head per query head")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear layers have one key head per value head")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_channels(self) -> int:
        h = self.linear_num_key_heads
        return h * (2 * self.linear_key_head_dim + self.linear_value_head_dim)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "OlmoHybridConfig":
        """Test-sized: two periods, float32, runs on the CPU in seconds."""
        return OlmoHybridConfig(
            vocab_size=vocab_size, hidden_size=128, intermediate_size=256,
            layer_types=PERIOD * 2, num_heads=4, num_kv_heads=4, head_dim=32,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=24, linear_value_head_dim=48,
            max_seq_len=512, dtype=jnp.float32, param_dtype=jnp.float32)


def _a_log_init(key, shape, dtype):
    """GatedDeltaNet: A uniform in (0, 16], held as its logarithm."""
    a = jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)
    return jnp.log(a).astype(dtype)


def _conv_init(key, shape, dtype):
    """torch's Conv1d default for a depthwise kernel of width 4."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer. `state` is None (no cache: the chunkwise
    form over the whole sequence) or the layer's (conv_tail, S) pool, with
    `rows` = the pool rows a prefill overwrites, or None for decode (one token
    for every row of the pool)."""
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, mask=None, state=None, rows=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        width = cfg.linear_conv_kernel_dim
        if mask is None:
            mask = jnp.ones((b, s), bool)
        proj = jnp.concatenate(
            [dense(cfg, h * dk, "q_proj")(x), dense(cfg, h * dk, "k_proj")(x),
             dense(cfg, h * dv, "v_proj")(x)], axis=-1)  # [B,S,C]
        taps = jnp.concatenate(
            [self.param(f"conv_{n}", _conv_init, (width, c), cfg.param_dtype)
             for n, c in (("q", h * dk), ("k", h * dk), ("v", h * dv))],
            axis=-1).astype(jnp.float32)  # [width, C]
        decode = state is not None and rows is None
        if decode:
            window = jnp.concatenate([state[0], proj], axis=1)  # [B,width,C]
            tail = jnp.where(mask[:, :, None], window[:, 1:], state[0])
        else:
            window = jnp.pad(proj, [(0, 0), (width - 1, 0), (0, 0)])
            # The last width-1 inputs before position true_len.
            true_len = jnp.sum(mask, axis=-1)
            tail = jnp.take_along_axis(
                window, (true_len[:, None] + jnp.arange(width - 1))[..., None],
                axis=1)
        conv = sum(window[:, j:j + s].astype(jnp.float32) * taps[j]
                   for j in range(width))
        conv = jax.nn.silu(conv)
        q, k, v = jnp.split(conv, [h * dk, 2 * h * dk], axis=-1)
        q = _l2norm(q.reshape(b, s, h, dk)) * dk ** -0.5
        k = _l2norm(k.reshape(b, s, h, dk))
        v = v.reshape(b, s, h, dv)
        a_log = self.param("A_log", _a_log_init, (h,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", dt_bias_init, (h,), cfg.param_dtype)
        f32 = lambda t: t.astype(jnp.float32)
        g = -jnp.exp(f32(a_log)) * jax.nn.softplus(
            f32(dense(cfg, h, "a_proj")(x)) + f32(dt_bias))
        beta = jax.nn.sigmoid(f32(dense(cfg, h, "b_proj")(x)))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        if decode:
            o, new_s = gdn_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state[1], mask[:, 0])
            o = o[:, None]
        else:
            # Padding changes nothing: no decay, nothing written.
            keep = mask[:, :, None]
            o, new_s = gdn_chunked(q, k, v, jnp.where(keep, g, 0.0),
                                   jnp.where(keep, beta, 0.0))
        new_state = None
        if decode:
            new_state = (tail, new_s)
        elif state is not None:
            new_state = (state[0].at[rows].set(tail.astype(state[0].dtype)),
                         state[1].at[rows].set(pack(new_s)))
        o = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="o_norm")(o)
        gate = dense(cfg, h * dv, "g_proj")(x).reshape(b, s, h, dv)
        y = (o * jax.nn.silu(f32(gate))).astype(cfg.dtype)
        return dense(cfg, cfg.hidden_size, "o_proj")(
            y.reshape(b, s, h * dv)), new_state


class FullAttention(nn.Module):
    """Causal softmax attention, one KV head per query head, RMSNorm on the
    projected q and k, no rotary embedding."""
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None):
        """`kv_pages`: this layer's (k_pages, v_pages) when serving, with
        `paged` = (page_table, write_mask, seq_lens); None for the whole
        sequence without a cache."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, d = cfg.num_heads, cfg.head_dim
        q = norm(cfg, "q_norm")(dense(cfg, h * d, "q_proj")(x))
        k = norm(cfg, "k_norm")(dense(cfg, h * d, "k_proj")(x))
        v = dense(cfg, h * d, "v_proj")(x)
        q, k, v = (t.reshape(b, s, h, d) for t in (q, k, v))
        if kv_pages is None:
            out = attention_reference(q, k, v, causal=True)
        else:
            page_table, write_mask, seq_lens = paged
            out, kv_pages = paged_write_attend(
                q, k, v, kv_pages, page_table, positions, write_mask,
                seq_lens)
        return dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages


class HybridLayer(nn.Module):
    """Olmo's post-norm block: each sub-layer's output is normalised before
    it joins the residual stream."""
    cfg: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, mask=None, cache=None, paged=None,
                 rows=None):
        cfg = self.cfg
        if self.kind == LINEAR:
            mixed, new_cache = GatedDeltaNet(cfg, name="linear_attn")(
                x, mask, cache, rows)
        else:
            mixed, new_cache = FullAttention(cfg, name="self_attn")(
                x, positions, cache, paged)
        x = x + norm(cfg, "post_attention_layernorm")(mixed)
        x = x + norm(cfg, "post_feedforward_layernorm")(
            Mlp(cfg, name="mlp")(x))
        return x, new_cache


class OlmoHybridModel(Decoder):
    cfg: OlmoHybridConfig

    @property
    def state_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.cfg.layer_types)
                     if kind == LINEAR)

    def init_cache(self, cache_cfg, mesh=None):
        """Per layer: (k_pages, v_pages) on a full layer; (conv_tail
        [max_seqs, 3, C], S [max_seqs, *state_shape(H, dk, dv)] float32, the
        heads laid along the lanes as `ops/linear_attention.py` says) on a
        linear one, a row per engine slot."""
        cfg = self.cfg
        return super().init_cache(
            cache_cfg, mesh,
            tail=(cfg.linear_conv_kernel_dim - 1, cfg.conv_channels),
            state=state_shape(cfg.linear_num_key_heads,
                              cfg.linear_key_head_dim,
                              cfg.linear_value_head_dim))

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg, [HybridLayer(cfg, kind) for kind in cfg.layer_types],
            {"norm": norm(cfg, None),
             "lm_head": dense(cfg, cfg.vocab_size, None)})

    @nn.compact
    def __call__(self, input_ids, positions=None, paged_kv=None,
                 page_table=None, write_mask=None, seq_lens=None, lora=None,
                 lora_idx=None, slots=None):
        """The engine's `apply` surface (`LlamaModel`'s). `paged_kv` is the
        list `init_cache` made; `slots` [nb] are the pool rows a prefill
        writes (state from zero), None when decoding one token for every row.
        Without `paged_kv`: the whole sequence, no cache."""
        cfg = self.cfg
        no_lora(self, lora)
        positions = batch_positions(input_ids, positions)
        x = embed(cfg, "embed_tokens")(input_ids)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i, kind in enumerate(cfg.layer_types):
            cache = paged_kv[i] if paged_kv is not None else None
            x, new_cache = HybridLayer(cfg, kind, name=f"layers_{i}")(
                x, positions, write_mask, cache, paged, slots)
            new_caches.append(new_cache)
        x = norm(cfg, "norm")(x)
        logits = dense(cfg, cfg.vocab_size, "lm_head")(x)
        if paged_kv is not None:
            return logits, new_caches
        return logits
