"""SDAR-MoE: the Qwen3-MoE decoder block (every feed-forward a dropless
top-k mixture of experts, no shared expert) generated from by diffusion over
blocks of `block_length` tokens.

Follows huggingface.co/JetLM/SDAR-30B-A3B-Chat's config.json (`model_type`
`sdar_moe`); parameter names are HF's, the experts of a layer held as two
stacks. What that config does not state is listed under `assumed` in
benchmark/configs/sdar-30b-a3b-serve.json: the RMSNorm over each head of q
and k (Qwen3's), the block length, the denoising steps, the remasking rule
and the mask token.

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    key j is visible to query i iff floor(j/B) <= floor(i/B) and j < length

Generation is the engine's: a block starts as MASK ids (after what is left
of the prompt), `denoising_steps` forwards over the block each reveal
`block_length / denoising_steps` of its masked positions by `remasking`
(`sequential`: the leftmost; `low_confidence_static`: the most confident).
The K/V the last of them leaves are those of ids still partly MASK, so the
block's K/V are stored under its revealed ids by one more forward over it,
which rides as further rows on the next block's first (`logits_from`: rows
that only store their K/V get no logits). The logits at a position are for
that position (no shift), and MASK itself is never generated: its logit is
minus infinity.

Serving cache (`Decoder.init_cache`): paged K/V on every layer, as
`LlamaModel`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (Decoder, SparseMoe, apply_rope,
                                   batch_positions, dense, embed, init_params,
                                   no_lora, norm)
from ray_tpu.ops.paged_attention import paged_write_attend

REMASKING = ("sequential", "low_confidence_static")

# Standard deviation of a seeded router's logits (its input has unit RMS).
# At 1 (lecun normal) the renormalised top-8 weights of 128 are nearly even,
# 0.23 down to 0.08; at 3 they fall from about 0.55 to 0.02, so a near-tie at
# the eighth place, which bf16 activations and a float32 reference decide
# differently now and then, swaps a fiftieth of the layer's output and not a
# twelfth (PERF.md section 6, PR 35: the readings at both).
ROUTER_LOGIT_STD = 3.0


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151_936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    max_seq_len: int = 32_768
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "sequential"
    mask_token_id: int = 151_935
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        b = self.block_length
        if b < 2 or b & (b - 1):
            raise ValueError(f"block_length {b} is not a power of two > 1")
        if b % self.denoising_steps:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} does not divide "
                f"block_length {b}")
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking {self.remasking!r} is none of "
                             f"{REMASKING}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id is not an id of the vocabulary")

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "SdarMoeConfig":
        """Test-sized: two layers, 16 experts, float32, seconds on the CPU."""
        return SdarMoeConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, moe_intermediate_size=32,
            num_experts=16, num_experts_per_tok=8, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=32, max_seq_len=512,
            mask_token_id=vocab_size - 1, dtype=jnp.float32,
            param_dtype=jnp.float32), **kw})


def block_attention(q, k, v, block_length: int):
    """Dense attention over a whole sequence under the block mask, without
    a cache: q [B,S,H,D], k and v [B,S,HK,D]."""
    h, hk = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(t, h // hk, axis=2).astype(jnp.float32)
            for t in (k, v))
    pos = jnp.arange(q.shape[1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k)
    logits = logits / math.sqrt(q.shape[-1])
    visible = pos[None, :] <= (pos[:, None] | (block_length - 1))
    probs = jax.nn.softmax(jnp.where(visible, logits, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)


class Attention(nn.Module):
    """Grouped-query attention with rotary positions and Qwen3's RMSNorm
    over each head of q and k, under the block mask."""
    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = dense(cfg, h * d, "q_proj")(x).reshape(b, s, h, d)
        k = dense(cfg, hk * d, "k_proj")(x).reshape(b, s, hk, d)
        v = dense(cfg, hk * d, "v_proj")(x).reshape(b, s, hk, d)
        q = apply_rope(norm(cfg, "q_norm")(q), positions, cfg.rope_theta)
        k = apply_rope(norm(cfg, "k_norm")(k), positions, cfg.rope_theta)
        if kv_pages is None:
            out = block_attention(q, k, v, cfg.block_length)
        else:
            page_table, write_mask, seq_lens = paged
            out, kv_pages = paged_write_attend(
                q, k, v, kv_pages, page_table, positions, write_mask,
                seq_lens, block_length=cfg.block_length)
        return dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages


class SdarMoeLayer(nn.Module):
    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None):
        cfg = self.cfg
        mixed, kv_pages = Attention(cfg, name="self_attn")(
            norm(cfg, "input_layernorm")(x), positions, kv_pages, paged)
        x = x + mixed
        x = x + SparseMoe(
            cfg, num_experts=cfg.num_experts,
            intermediate=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok, router_std=ROUTER_LOGIT_STD,
            name="mlp")(norm(cfg, "post_attention_layernorm")(x))
        return x, kv_pages


class SdarMoeModel(Decoder):
    cfg: SdarMoeConfig

    # What the engine reads to generate by blocks.
    block_length = property(lambda self: self.cfg.block_length)
    denoising_steps = property(lambda self: self.cfg.denoising_steps)
    remasking = property(lambda self: self.cfg.remasking)
    mask_token_id = property(lambda self: self.cfg.mask_token_id)

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg, [SdarMoeLayer(cfg)] * cfg.num_layers,
            {"norm": norm(cfg, None),
             "lm_head": dense(cfg, cfg.vocab_size, None)},
            length=cfg.block_length)

    @nn.compact
    def __call__(self, input_ids, positions=None, paged_kv=None,
                 page_table=None, write_mask=None, seq_lens=None, lora=None,
                 lora_idx=None, slots=None, logits_from: int = 0):
        """The engine's `apply` surface (`LlamaModel`'s). Without
        `paged_kv`: the whole sequence under the block mask, no cache. The
        rows before `logits_from` go through the layers for their K/V alone:
        the final norm and the head run over the others."""
        cfg = self.cfg
        no_lora(self, lora)
        positions = batch_positions(input_ids, positions)
        x = embed(cfg, "embed_tokens")(input_ids)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i in range(cfg.num_layers):
            kv_pages = paged_kv[i] if paged_kv is not None else None
            x, kv_pages = SdarMoeLayer(cfg, name=f"layers_{i}")(
                x, positions, kv_pages, paged)
            new_caches.append(kv_pages)
        x = norm(cfg, "norm")(x[logits_from:])
        logits = dense(cfg, cfg.vocab_size, "lm_head")(x)
        logits = jnp.where(jnp.arange(cfg.vocab_size) == cfg.mask_token_id,
                           -jnp.inf, logits)
        if paged_kv is not None:
            return logits, new_caches
        return logits
