"""MiniCPM-SALA (`model_type` `minicpm_sala`): a pre-norm decoder under
MiniCPM's three multipliers whose mixers are of two kinds (`mixer_types`):
`lightning-attn`, a linear attention with a constant decay a head that
remembers a sequence in one state a head, and `minicpm4`, a grouped-query
softmax attention without rotary embedding in which a query that sees
`dense_len` keys or more attends to the `topk` key blocks it chooses by
compressed keys (InfLLM-V2, arXiv:2506.07900) and not to all of them.

Follows huggingface.co/openbmb/MiniCPM-SALA's config.json; parameter names
are HF's (MiniCPM's). What the config does not state is listed under
`assumed` in benchmark/configs/minicpm-sala-serve.json: the selection's sizes
(MiniCPM4's `sparse_config`), the switch read a position at a time, the
compressed scores normalised by their own sum, the decay's geometric series,
the norms' and gates' shapes.

    block      a = x + s mixer(RMSNorm(x));  out = a + s Mlp(RMSNorm(a)),
               s = scale_depth / sqrt(depth);  x0 = scale_emb E[ids];  logits
               = (RMSNorm(x) / (hidden / dim_model_base)) W_head
    lightning  q, k, v = u W_q, u W_k, u W_v a head of 128; RMSNorm over each
               head of q and k, both rotated;  S_t = lambda_h S_(t-1) + k_t^T
               v_t, o_t = 128^-0.5 q_t S_t, lambda_h = exp(-2^(-8 (h+1) / H));
               y = RMSNorm_hidden(concat o) * sigmoid(u W_g);  y W_o
    minicpm4   q a head of 128 on `num_kv_heads` KV heads, RMSNorm over each
               head of q and k, no rotation;  the query at t with t + 1 <
               dense_len: causal softmax over keys 0..t;  else over the keys
               of the `topk` blocks its KV head's group chose
               (`ops.attention.compressed_scores`, `chosen_mask`: a block
               is in where fewer than `topk` blocks beat it, ties to the
               lower index, found by counting and never by a sort);
               o * sigmoid(u W_g);  W_o

Serving cache, per layer (`Decoder.init_cache`): a lightning layer holds the
float32 state [heads, 128, 128] of each engine slot (prefill overwrites a
slot's row from zero, decode updates every row in place); a sparse layer
paged K/V from the engine's allocator and beside them the index pool, a
page's segment means (`ops/paged_attention.py`). A page is a selection
block. A prefill runs a row of its wave at a time through all the layers
(the hidden states of 8 x 16,384 positions would be 1 GiB a copy), over the
row's own q, k, v: `lightning_chunked`, and `select_blocks` (the counted
mask, a tile of 512 queries at a time) with the `sparse_flash` kernel, no
scores tensor; then the wave's states, keys,
values and whole segments' means are written. It reads no cached page, so
the engine shares no prefix for this family. A decode step updates the
states (`lightning_step`), writes its token's K/V and the segment mean it
completes, chooses pages (`select_pages`: the same mask over a row's own
pages, listed in ascending order by `choose_blocks`) and walks them
(`sparse_decode`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (Decoder, Mlp, apply_rope, batch_positions,
                                   dense, embed, init_params, no_lora, norm)
from ray_tpu.ops.attention import (SparseSizes, select_blocks,
                                   sparse_attention_plain,
                                   sparse_flash_attention)
from ray_tpu.ops.linear_attention import lightning_chunked, lightning_step
from ray_tpu.ops.paged_attention import (index_write, paged_write,
                                         sparse_write_attend)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# The published 32 layers' mixers: sparse at 0, 9, 16, 17, 22, 29, 30, 31.
PUBLISHED_MIXERS = tuple(SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31)
                         else LIGHTNING for i in range(32))


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73_448
    hidden_size: int = 4096
    intermediate_size: int = 16_384
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    # The published depth, which the residual multiplier is reckoned from
    # however many of the layers are held here.
    depth: int = 32
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10_000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # The sparse mixer's selection (`ops.attention.SparseSizes`).
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    max_seq_len: int = 524_288
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # (a list from a JSON file hashes as a tuple does)
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if bad:
            raise ValueError(f"mixer_types holds {sorted(bad)}")
        self.sizes.check()

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def sizes(self) -> SparseSizes:
        return SparseSizes(self.kernel_size, self.kernel_stride,
                           self.block_size, self.init_blocks,
                           self.window_size, self.topk, self.dense_len)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth)

    @property
    def log_decay(self) -> jax.Array:
        """log lambda_h [lightning_heads]: Lightning Attention's geometric
        series of slopes, -2^(-8 (h + 1) / H)."""
        h = self.lightning_heads
        return -jnp.exp2(-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1.0) / h)

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "MiniCPMSalaConfig":
        """Test-sized: one sparse layer among three lightning ones, a
        selection of 4 blocks of 16 from 64 positions on, float32, seconds
        on the CPU."""
        return MiniCPMSalaConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            mixer_types=(LIGHTNING, SPARSE, LIGHTNING, LIGHTNING), depth=4,
            num_heads=4, num_kv_heads=2, head_dim=16, lightning_heads=4,
            lightning_head_dim=16, dim_model_base=32, kernel_size=8,
            kernel_stride=4, block_size=16, init_blocks=1, window_size=32,
            topk=4, dense_len=64, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32), **kw})


class LightningAttention(nn.Module):
    """The linear-attention mixer. Without `state`: the chunkwise form over
    the whole sequence from a zero state (`lengths` [B]: where a row's
    padding begins), returning the last states [B, H, D, D]. With the
    layer's pool [slots, H, D, D]: one token for every row of it that is
    `active` [B], returning the pool."""
    cfg: MiniCPMSalaConfig

    @nn.compact
    def __call__(self, x, positions, lengths=None, state=None, active=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, d = cfg.lightning_heads, cfg.lightning_head_dim
        heads = lambda name: dense(cfg, h * d, name)(x).reshape(b, s, h, d)
        rope = lambda t: apply_rope(t, positions, cfg.rope_theta)
        q = rope(norm(cfg, "q_norm")(heads("q_proj")))
        k = rope(norm(cfg, "k_norm")(heads("k_proj")))
        v = heads("v_proj")
        if state is None:
            o, state = lightning_chunked(q, k, v, cfg.log_decay, lengths)
        else:
            o, state = lightning_step(q[:, 0], k[:, 0], v[:, 0],
                                      cfg.log_decay, state, active)
            o = o[:, None]
        y = norm(cfg, "o_norm")(o.reshape(b, s, h * d) * d ** -0.5)
        y = y * jax.nn.sigmoid(dense(cfg, h * d, "g_proj")(x))
        return dense(cfg, cfg.hidden_size, "o_proj")(y), state


class SparseAttention(nn.Module):
    """The `minicpm4` mixer. Without `cache`: the whole sequence over its own
    keys (`flash`: through the `sparse_flash` kernel), returning the (k, v)
    a cache would hold; with the layer's (k_pages, v_pages, m_pages) and
    `paged` = (page_table, write_mask, seq_lens): one decode step, returning
    the cache."""
    cfg: MiniCPMSalaConfig
    flash: bool = False

    @nn.compact
    def __call__(self, x, positions, cache=None, paged=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = norm(cfg, "q_norm")(
            dense(cfg, h * d, "q_proj")(x).reshape(b, s, h, d))
        k = norm(cfg, "k_norm")(
            dense(cfg, hk * d, "k_proj")(x).reshape(b, s, hk, d))
        v = dense(cfg, hk * d, "v_proj")(x).reshape(b, s, hk, d)
        if cache is not None:
            page_table, write_mask, seq_lens = paged
            out, kept, load = sparse_write_attend(
                q, k, v, cache, page_table, positions, write_mask, seq_lens,
                cfg.sizes)
            # (pages selected, pages visible) of this call, for whoever asks
            # for the collection (the engine's decode program).
            self.sow("page_load", "load", load)
        else:
            chosen = select_blocks(q, k, cfg.sizes)
            if self.flash:
                out = sparse_flash_attention(q, k, v, chosen,
                                             block_size=cfg.block_size)
            else:
                out = sparse_attention_plain(q, k, v, chosen, cfg.block_size)
            kept = (k, v)
        out = out.reshape(b, s, h * d) * jax.nn.sigmoid(
            dense(cfg, h * d, "g_proj")(x))
        return dense(cfg, cfg.hidden_size, "o_proj")(out), kept


class MiniCPMSalaLayer(nn.Module):
    cfg: MiniCPMSalaConfig
    kind: str
    flash: bool = False

    @nn.compact
    def __call__(self, x, positions, lengths=None, cache=None, paged=None):
        """`cache` with `paged` = (page_table, write_mask, seq_lens): a decode
        step over the layer's cache; without: the whole sequence (`lengths`
        [B]: where a row's padding begins). -> (out, the layer's cache after
        the step, or what a cache would be given of the sequence: a
        lightning layer's last states, a sparse layer's (k, v))."""
        cfg = self.cfg
        u = norm(cfg, "input_layernorm")(x)
        if self.kind == LIGHTNING:
            mixed, kept = LightningAttention(cfg, name="self_attn")(
                u, positions, lengths, cache,
                None if cache is None else paged[1][:, 0])
        else:
            mixed, kept = SparseAttention(cfg, self.flash, name="self_attn")(
                u, positions, cache, paged)
        x = x + cfg.residual_scale * mixed
        x = x + cfg.residual_scale * Mlp(cfg, name="mlp")(
            norm(cfg, "post_attention_layernorm")(x))
        return x, kept


def _prefill_row(model, carry, row):
    """One row of a prefill's wave through the embedding and every layer,
    as `nn.scan` calls it: (ids [S], positions [S], the prompt's length, the
    position the head wants) -> (that position's hidden state, every
    layer's `kept`)."""
    ids, positions, length, at = row
    x = model.embedded(ids)
    kept = []
    for i, kind in enumerate(model.cfg.mixer_types):
        x, held = MiniCPMSalaLayer(model.cfg, kind, flash=True,
                                   name=f"layers_{i}")(
            x[None], positions[None], length[None])
        x = x[0]
        kept.append(jax.tree.map(lambda t: t[0], held))
    return carry, (jnp.take(x, at, axis=0), kept)


# A prefill's wave a row at a time, the parameters shared.
_row_by_row = nn.scan(_prefill_row, variable_broadcast="params",
                      split_rngs={"params": False})


class MiniCPMSalaModel(Decoder):
    cfg: MiniCPMSalaConfig

    # A prefill wants the head on a row's last position only (the logits of
    # a wave's 131,072 positions over 73,448 ids would be 19 GB).
    num_logits_to_keep = 1
    index_segments = property(lambda self: self.cfg.sizes.per_block)

    @property
    def state_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.cfg.mixer_types)
                     if kind == LIGHTNING)

    @property
    def index_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.cfg.mixer_types)
                     if kind == SPARSE)

    def init_cache(self, cache_cfg, mesh=None):
        """Per layer: the state [max_seqs, H, D, D] float32 on a lightning
        layer, a row per engine slot; (k_pages, v_pages, m_pages) on a
        sparse one, whose page is a selection block."""
        cfg = self.cfg
        if mesh is None and cache_cfg.page_size != cfg.block_size:
            raise ValueError(
                f"page_size {cache_cfg.page_size} is not {type(self).__name__}"
                f"'s block_size {cfg.block_size}: a page is a selection block")
        return super().init_cache(
            cache_cfg, mesh, state=(cfg.lightning_heads,
                                    cfg.lightning_head_dim,
                                    cfg.lightning_head_dim))

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg, [MiniCPMSalaLayer(cfg, kind)
                       for kind in cfg.mixer_types],
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
        new_caches = []
        if paged_kv is not None and slots is not None:
            _, (x, kept) = _row_by_row(self, None, (
                input_ids, positions, seq_lens, logits_at))
            x = x[:, None]
            for i, kind in enumerate(cfg.mixer_types):
                new_caches.append(self._written(
                    kind, paged_kv[i], kept[i], slots, page_table, positions,
                    write_mask))
        else:
            x = self.embedded(input_ids)
            paged = (page_table, write_mask, seq_lens)
            for i, kind in enumerate(cfg.mixer_types):
                layer = MiniCPMSalaLayer(cfg, kind, name=f"layers_{i}")
                if paged_kv is None:
                    x, _ = layer(x, positions)
                else:
                    x, cache = layer(x, positions, None, paged_kv[i], paged)
                    new_caches.append(cache)
            if logits_at is not None:
                x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = norm(cfg, "norm")(x) / (cfg.hidden_size / cfg.dim_model_base)
        logits = dense(cfg, cfg.vocab_size, "lm_head")(x)
        if paged_kv is not None:
            return logits, new_caches
        return logits

    def embedded(self, ids):
        return embed(self.cfg, "embed_tokens")(ids) * self.cfg.scale_emb

    @nn.nowrap
    def _written(self, kind, cache, kept, slots, page_table, positions,
                 write_mask):
        """A layer's cache with what a prefill's wave leaves in it."""
        if kind == LIGHTNING:
            return cache.at[slots].set(kept)
        k, v = kept
        k_pages, v_pages, m_pages = cache
        write = lambda pages, new: paged_write(pages, new, page_table,
                                               positions, write_mask)
        return (write(k_pages, k), write(v_pages, v),
                index_write(m_pages, k, page_table, positions, write_mask,
                            self.cfg.block_size))
