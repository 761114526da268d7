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
    mamba       `layers.Mamba2Mixer` with one group of B and C (the gated
                norm over all of d_inner)
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
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (Decoder, Mamba2Mixer, SparseMoe,
                                   batch_positions, dense, embed, init_params,
                                   no_lora, norm)
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.paged_attention import paged_attention, paged_write

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
        q = dense(cfg, h * d, "q_proj")(x).reshape(b, s, h, d)
        k = dense(cfg, hk * d, "k_proj")(x).reshape(b, s, hk, d)
        v = dense(cfg, hk * d, "v_proj")(x).reshape(b, s, hk, d)
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
        return dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages


class SharedMlp(nn.Module):
    """The shared expert: a SwiGLU every token goes through whole."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate, up = jnp.split(dense(cfg, 2 * cfg.shared_intermediate_size,
                                    "input_linear")(x), 2, axis=-1)
        return dense(cfg, cfg.hidden_size, "output_linear")(
            nn.silu(gate) * up)


class GraniteHybridLayer(nn.Module):
    """A pre-norm block: the mixer, then the routed experts beside the
    shared one, each branch times `residual_multiplier`."""
    cfg: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, mask=None, cache=None, paged=None,
                 rows=None):
        cfg = self.cfg
        r = cfg.residual_multiplier
        normed = norm(cfg, "input_layernorm")(x)
        if self.kind == MAMBA:
            mixed, new_cache = Mamba2Mixer(cfg, name="mamba")(
                normed, mask, cache, rows)
        else:
            mixed, new_cache = Attention(cfg, name="self_attn")(
                normed, positions, cache, paged)
        x = x + (r * mixed).astype(cfg.dtype)
        u = norm(cfg, "post_attention_layernorm")(x)
        held = cfg.experts_held
        routed = SparseMoe(
            cfg, num_experts=cfg.num_experts,
            intermediate=cfg.intermediate_size,
            top_k=cfg.num_experts_per_tok, router_std=ROUTER_LOGIT_STD,
            held=None if held[1] == cfg.num_experts else held,
            name="block_sparse_moe")
        ff = routed(u) + SharedMlp(cfg, name="shared_mlp")(u)
        return x + (r * ff).astype(cfg.dtype), new_cache


class GraniteHybridModel(Decoder):
    cfg: GraniteHybridConfig

    # A prefill wants the head on a row's last position only (the logits of
    # a wave's 16,384 positions over 100,352 ids would be 6.6 GB).
    num_logits_to_keep = 1

    @property
    def state_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.cfg.layer_types)
                     if kind == MAMBA)

    @property
    def expert_layer_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.cfg.num_layers))

    def init_cache(self, cache_cfg, mesh=None):
        """Per layer: (k_pages, v_pages) on an attention layer; (conv_tail
        [max_seqs, 3, d_inner + 2 N], S [max_seqs, heads, d_head, N] float32)
        on a Mamba layer, a row per engine slot, the states minor."""
        cfg = self.cfg
        return super().init_cache(
            cache_cfg, mesh, tail=(cfg.mamba_d_conv - 1, cfg.conv_dim),
            state=(cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state))

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg,
            [GraniteHybridLayer(cfg, kind) for kind in cfg.layer_types],
            {"norm": norm(cfg, None)})

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
        x = (cfg.embedding_multiplier * table(input_ids)).astype(cfg.dtype)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i, kind in enumerate(cfg.layer_types):
            cache = paged_kv[i] if paged_kv is not None else None
            x, new_cache = GraniteHybridLayer(cfg, kind, name=f"layers_{i}")(
                x, positions, write_mask, cache, paged, slots)
            new_caches.append(new_cache)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = table.attend(norm(cfg, "norm")(x)) / cfg.logits_scaling
        if paged_kv is not None:
            return logits, new_caches
        return logits
