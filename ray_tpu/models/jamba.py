"""Jamba: a decoder whose layers are of two kinds, a Mamba-1 selective state
space on most of them and softmax attention on every `attn_layer_period`-th
(arXiv:2403.19887), each followed by a SwiGLU MLP (`num_experts` is 1 in the
published 3B config: no layer routes).

Follows huggingface.co/ai21labs/AI21-Jamba2-3B's config.json and HF's
`modeling_jamba.py`; parameter names are HF's. What the config does not state
is listed under `assumed` in benchmark/configs/jamba2-3b-serve.json: the head
size, the order of the layers from offset and period, Mamba's initial ranges,
which leaves are float32.

    layer i     attention where i % attn_layer_period == attn_layer_offset
    block       h = x + mixer(RMSNorm(x));  out = h + mlp(RMSNorm(h))
    attention   multi-query: 20 heads of 128 over one K/V head, no bias, no
                positional encoding (the state space layers carry position)
    mamba       (x, z) = in_proj(u); x = silu(conv4(x) + b); (dt, B, C) =
                x_proj(x), each RMS-normed; dt = softplus(dt_proj(dt) + b_dt);
                the recurrence of ops/ssm.py with A = -exp(A_log); out_proj
    head        the embedding transposed, on a prefill's last position only
                (`num_logits_to_keep`)

Serving cache, per layer (`init_cache`): an attention layer holds paged K/V
like `LlamaModel`; a Mamba layer holds, per engine slot, the last three
inputs of its convolution and the float32 state [d_state, d_inner], the
channels on the lanes. A state row is the slot's index: prefill overwrites
the rows it is given from zero, decode updates every active row in place.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (Decoder, Mlp, batch_positions, conv_init,
                                   dense, dt_bias_init, embed, init_params,
                                   no_lora, norm)
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.paged_attention import paged_write_attend
from ray_tpu.ops.ssm import causal_conv, ssm_scan, ssm_scan_plain, ssm_step

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65_536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_seq_len: int = 262_144
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_layers))

    @staticmethod
    def tiny(vocab_size: int = 512) -> "JambaConfig":
        """Test-sized: one period of four, float32, seconds on the CPU."""
        return JambaConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=4, attn_layer_period=4, attn_layer_offset=2,
            num_heads=5, num_kv_heads=1, head_dim=16, mamba_dt_rank=8,
            max_seq_len=512, dtype=jnp.float32, param_dtype=jnp.float32)


def _a_log_init(key, shape, dtype):
    """Mamba: A = -(1 ... d_state) on every channel, held as log(-A)."""
    n, d = shape
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
        (n, d)).astype(dtype)


class MambaMixer(nn.Module):
    """`state` is None (no cache: the recurrence token by token over the
    whole sequence) or the layer's (conv_tail, h) pool, with `rows` = the pool
    rows a prefill overwrites, or None for decode (one token for every row of
    the pool)."""
    cfg: JambaConfig

    @nn.compact
    def __call__(self, u, mask=None, state=None, rows=None):
        cfg = self.cfg
        b, s, _ = u.shape
        d, n, rank, width = (cfg.d_inner, cfg.mamba_d_state,
                             cfg.mamba_dt_rank, cfg.mamba_d_conv)
        f32 = lambda t: t.astype(jnp.float32)
        if mask is None:
            mask = jnp.ones((b, s), bool)
        x, z = jnp.split(dense(cfg, 2 * d, "in_proj")(u), 2, axis=-1)
        taps = self.param("conv1d_weight", conv_init, (width, d),
                          cfg.param_dtype)
        bias = self.param("conv1d_bias", conv_init, (d,), cfg.param_dtype)
        decode = state is not None and rows is None
        conv, window = causal_conv(x, taps, bias,
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
        x = jnp.where(mask[:, :, None], jax.nn.silu(conv), 0.0).astype(
            cfg.dtype)
        dt, bm, cm = jnp.split(dense(cfg, rank + 2 * n, "x_proj")(x),
                               [rank, rank + n], axis=-1)
        dt = norm(cfg, "dt_layernorm")(dt)
        bm = norm(cfg, "b_layernorm")(bm)
        cm = norm(cfg, "c_layernorm")(cm)
        dt_bias = self.param("dt_bias", dt_bias_init, (d,), jnp.float32)
        dt = jax.nn.softplus(f32(dense(cfg, d, "dt_proj")(dt)) + dt_bias)
        dt = jnp.where(mask[:, :, None], dt, 0.0)
        a = -jnp.exp(f32(self.param("A_log", _a_log_init, (n, d),
                                    jnp.float32)))
        skip = f32(self.param("D", nn.initializers.ones, (d,), jnp.float32))
        new_state = None
        if decode:
            y, h = ssm_step(x[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], z[:, 0], a,
                            skip, state[1], mask[:, 0])
            y, new_state = y[:, None], (tail, h)
        elif state is None:
            y, _ = ssm_scan_plain(x, dt, bm, cm, z, a, skip)
        else:
            y, h = ssm_scan(x, dt, bm, cm, z, a, skip, true_len)
            new_state = (state[0].at[rows].set(tail.astype(state[0].dtype)),
                         state[1].at[rows].set(h))
        return dense(cfg, cfg.hidden_size, "out_proj")(
            y.astype(cfg.dtype)), new_state


class Attention(nn.Module):
    """Causal softmax attention, `num_heads` query heads over `num_kv_heads`
    K/V heads, no bias, no positional encoding."""
    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None):
        """`kv_pages`: this layer's (k_pages, v_pages) when serving, with
        `paged` = (page_table, write_mask, seq_lens); None for the whole
        sequence without a cache."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = dense(cfg, h * d, "q_proj")(x).reshape(b, s, h, d)
        k = dense(cfg, hk * d, "k_proj")(x).reshape(b, s, hk, d)
        v = dense(cfg, hk * d, "v_proj")(x).reshape(b, s, hk, d)
        if kv_pages is None:
            out = attention_reference(q, k, v, causal=True)
        else:
            page_table, write_mask, seq_lens = paged
            out, kv_pages = paged_write_attend(
                q, k, v, kv_pages, page_table, positions, write_mask,
                seq_lens)
        return dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages


class JambaLayer(nn.Module):
    """A pre-norm block: the mixer, then the MLP, each on the normalised
    stream and added to it."""
    cfg: JambaConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, mask=None, cache=None, paged=None,
                 rows=None):
        cfg = self.cfg
        normed = norm(cfg, "input_layernorm")(x)
        if self.kind == MAMBA:
            mixed, new_cache = MambaMixer(cfg, name="mamba")(
                normed, mask, cache, rows)
        else:
            mixed, new_cache = Attention(cfg, name="self_attn")(
                normed, positions, cache, paged)
        x = x + mixed
        x = x + Mlp(cfg, name="feed_forward")(
            norm(cfg, "pre_ff_layernorm")(x))
        return x, new_cache


class JambaModel(Decoder):
    cfg: JambaConfig

    # The published config's own `num_logits_to_keep`.
    num_logits_to_keep = 1

    @property
    def state_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.cfg.layer_kinds)
                     if kind == MAMBA)

    def init_cache(self, cache_cfg, mesh=None):
        """Per layer: (k_pages, v_pages) on an attention layer; (conv_tail
        [max_seqs, 3, d_inner], h [max_seqs, d_state, d_inner] float32) on a
        Mamba layer, a row per engine slot, the channels minor."""
        cfg = self.cfg
        return super().init_cache(
            cache_cfg, mesh, tail=(cfg.mamba_d_conv - 1, cfg.d_inner),
            state=(cfg.mamba_d_state, cfg.d_inner))

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg, [JambaLayer(cfg, kind) for kind in cfg.layer_kinds],
            {"final_layernorm": norm(cfg, None)})

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
        no_lora(self, lora)
        positions = batch_positions(input_ids, positions)
        table = embed(cfg, "embed_tokens")
        x = table(input_ids)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i, kind in enumerate(cfg.layer_kinds):
            cache = paged_kv[i] if paged_kv is not None else None
            x, new_cache = JambaLayer(cfg, kind, name=f"layers_{i}")(
                x, positions, write_mask, cache, paged, slots)
            new_caches.append(new_cache)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = table.attend(norm(cfg, "final_layernorm")(x))
        if paged_kv is not None:
            return logits, new_caches
        return logits
