"""Mellum 2 (`model_type` `mellum`): the Qwen3-MoE decoder block (per-head
RMSNorm on q and k, every feed-forward a dropless top-k mixture of experts,
no shared expert) whose attention layers are of two kinds (`layer_types`):
`sliding_attention`, where a query sees the last `sliding_window` keys and
the rotary base is plain, and `full_attention`, causal over everything under
YaRN-scaled rotary frequencies.

Follows huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct's config.json;
parameter names are HF's (Qwen3-MoE's), the experts of a layer held as two
stacks. What the config does not state is listed under `assumed` in
benchmark/configs/mellum2-12b-a2.5b-serve.json (the per-head norms, the
window counting the query's own position, the MTP head left out).

    block     a = x + Attn_kind(RMSNorm(x));  y = a + MoE(RMSNorm(a))
    sliding   key j visible to query i iff 0 <= i - j < sliding_window;
              inv_freq = theta^(-2j/d)
    full      key j visible iff j <= i; inv_freq YaRN's (`rope_freqs`), cos
              and sin times `yarn_attention_factor`

Serving cache, per layer (`Decoder.init_cache`): a full layer holds paged
K/V from the engine's allocator like `LlamaModel`; a sliding layer a ring of
`sliding_window / page_size + 1` pages a slot (`ops/paged_attention.py`
`ring_*`), whose bytes do not grow with the context. A prefill attends over
the call's own q, k, v (the flash forward kernel, causal or banded: no
scores tensor, and a ring could not be attended after a long prompt has
wrapped it) and then writes what has to stay; it never runs on a cached
prefix (the engine shares no prefix for a model with rings). A decode step
writes its token and walks the pages (`paged_decode`) or the window's part
of the ring (`swa_decode`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (Decoder, SparseMoe, apply_rope,
                                   batch_positions, dense, embed, init_params,
                                   no_lora, norm, rope_freqs)
from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   sliding_window_attention)
from ray_tpu.ops.paged_attention import (paged_write, paged_write_attend,
                                         ring_attention, ring_write)

SLIDING, FULL = "sliding_attention", "full_attention"

# Standard deviation of a seeded router's logits (its input has unit RMS),
# sharpened as models/sdar_moe.py and models/granite_hybrid.py sharpen theirs
# and for their reason: at 1 (lecun normal) the renormalised top-8 weights of
# 64 are nearly even, so a near-tie at the eighth place, which bf16
# activations and a float32 reference decide differently now and then, swaps
# a tenth of the layer's output; at 4 they fall from 0.6 to 0.011. Not more:
# on the chip the check's sound readings were 0.03-0.20 over 40 seeds at 4
# against 0.33-0.98 in four significant bits, but 0.04-0.60 against 0.45-1.6
# at 5 and 0.05-0.63 against 0.41-2.9 at 6 (a sharper router multiplies the
# rounding of its own logits): no limit stands there (PERF.md section 6,
# PR 44).
ROUTER_LOGIT_STD = 4.0


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98_304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    layer_types: Tuple[str, ...] = ((SLIDING,) * 3 + (FULL,)) * 7
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta: float = 500_000.0
    # `rope_parameters.full_attention` (YaRN); the sliding layers' entry is
    # the plain `rope_theta`.
    yarn_factor: float = 16.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    max_seq_len: int = 131_072
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # (a list from a JSON file hashes as a tuple does)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def rope(self, kind: str) -> Tuple[jax.Array, float]:
        """(inverse frequencies [head_dim / 2], factor on cos and sin) of a
        layer kind."""
        if kind == SLIDING:
            return rope_freqs(self.head_dim, self.rope_theta), 1.0
        return rope_freqs(self.head_dim, self.rope_theta, (
            self.yarn_factor, self.yarn_original_max_position_embeddings,
            self.yarn_beta_fast, self.yarn_beta_slow)), \
            self.yarn_attention_factor

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "MellumConfig":
        """Test-sized: one period, 16 experts top-4, a window of 8, float32,
        seconds on the CPU."""
        return MellumConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, moe_intermediate_size=32,
            num_experts=16, num_experts_per_tok=4,
            layer_types=(SLIDING,) * 3 + (FULL,), num_heads=4,
            num_kv_heads=2, head_dim=32, sliding_window=8,
            yarn_original_max_position_embeddings=16, max_seq_len=512,
            dtype=jnp.float32, param_dtype=jnp.float32), **kw})


class Attention(nn.Module):
    """Grouped-query attention with Qwen3's RMSNorm over each head of q and
    k and the rotary frequencies of the layer's `kind`; causal, and inside
    the window on a sliding layer."""
    cfg: MellumConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None, slots=None):
        """`kv_pages`: this layer's (k, v) pages or rings when serving, with
        `paged` = (page_table, write_mask, seq_lens) and `slots` [B] the
        engine slots a prefill's rows belong to (None: a decode step, row r
        is slot r); None for the whole sequence without a cache."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.kind == SLIDING else None
        freqs, factor = cfg.rope(self.kind)
        rope = lambda t: apply_rope(t, positions, cfg.rope_theta, freqs,
                                    factor)
        q = dense(cfg, h * d, "q_proj")(x).reshape(b, s, h, d)
        k = dense(cfg, hk * d, "k_proj")(x).reshape(b, s, hk, d)
        v = dense(cfg, hk * d, "v_proj")(x).reshape(b, s, hk, d)
        q, k = rope(norm(cfg, "q_norm")(q)), rope(norm(cfg, "k_norm")(k))
        if kv_pages is None:
            out = attention_reference(q, k, v, causal=True, window=window)
        else:
            out, kv_pages = self._serve(q, k, v, kv_pages, positions, paged,
                                        slots, window)
        return dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages

    def _serve(self, q, k, v, kv_pages, positions, paged, slots, window):
        page_table, write_mask, seq_lens = paged
        decode = slots is None
        if decode:
            slots = jnp.arange(q.shape[0], dtype=jnp.int32)
        if window is None:
            if decode:
                return paged_write_attend(q, k, v, kv_pages, page_table,
                                          positions, write_mask, seq_lens)
            write = lambda pages, new: paged_write(
                pages, new, page_table, positions, write_mask)
        else:
            write = lambda pages, new: ring_write(
                pages, new, slots, positions, write_mask, seq_lens, window)
        k_pages, v_pages = write(kv_pages[0], k), write(kv_pages[1], v)
        if decode:
            out = ring_attention(q, k_pages, v_pages, slots, seq_lens, window)
        elif window is None:
            # The call's own keys: padding lies past every real query.
            out = flash_attention(q, k, v, causal=True)
        else:
            out = sliding_window_attention(q, k, v, window=window)
        return out, (k_pages, v_pages)


class MellumLayer(nn.Module):
    cfg: MellumConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None, slots=None):
        cfg = self.cfg
        mixed, kv_pages = Attention(cfg, self.kind, name="self_attn")(
            norm(cfg, "input_layernorm")(x), positions, kv_pages, paged,
            slots)
        x = x + mixed
        x = x + SparseMoe(
            cfg, num_experts=cfg.num_experts,
            intermediate=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok, router_std=ROUTER_LOGIT_STD,
            name="mlp")(norm(cfg, "post_attention_layernorm")(x))
        return x, kv_pages


class MellumModel(Decoder):
    cfg: MellumConfig

    # A prefill wants the head on a row's last position only (the logits of
    # a wave's 32,768 positions over 98,304 ids would be 12.9 GB).
    num_logits_to_keep = 1
    sliding_window = property(lambda self: self.cfg.sliding_window)

    @property
    def ring_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.cfg.layer_types)
                     if kind == SLIDING)

    @property
    def expert_layer_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.cfg.num_layers))

    @nn.nowrap
    def init_params(self, rng):
        """Both kinds of layer hold the same tensors: one compiled
        initializer."""
        cfg = self.cfg
        return init_params(
            rng, cfg, [MellumLayer(cfg, FULL)] * cfg.num_layers,
            {"norm": norm(cfg, None),
             "lm_head": dense(cfg, cfg.vocab_size, None)})

    @nn.compact
    def __call__(self, input_ids, positions=None, paged_kv=None,
                 page_table=None, write_mask=None, seq_lens=None, lora=None,
                 lora_idx=None, slots=None, logits_at=None):
        """The engine's `apply` surface (`LlamaModel`'s). `paged_kv` is the
        list `init_cache` made; `slots` [nb] are the engine slots of a
        prefill's rows (positions from 0: no cached prefix), None when
        decoding one token for every slot. `logits_at` [B]: the one position
        of each row the final norm and the head run on (logits [B, 1, V]);
        None: every position. Without `paged_kv`: the whole sequence, no
        cache."""
        cfg = self.cfg
        no_lora(self, lora)
        positions = batch_positions(input_ids, positions)
        x = embed(cfg, "embed_tokens")(input_ids)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i, kind in enumerate(cfg.layer_types):
            kv_pages = paged_kv[i] if paged_kv is not None else None
            x, kv_pages = MellumLayer(cfg, kind, name=f"layers_{i}")(
                x, positions, kv_pages, paged, slots)
            new_caches.append(kv_pages)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = dense(cfg, cfg.vocab_size, "lm_head")(norm(cfg, "norm")(x))
        if paged_kv is not None:
            return logits, new_caches
        return logits
