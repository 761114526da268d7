"""Sarvam-105B (`model_type` `sarvam_mla`): a pre-norm decoder whose attention
is latent (MLA: keys and values are up-projections of one 512-wide latent a
token, beside one 64-wide rotary key all heads share), whose first layer has
a dense SwiGLU MLP and every other a sigmoid-routed mixture of experts beside
a shared expert.

Follows huggingface.co/sarvamai/sarvam-105b's config.json; parameter names
are HF's (DeepSeek-V2's, whose keys these are), the experts of a layer held
as two stacks. What the config does not state is listed under `assumed` in
benchmark/configs/sarvam-105b-serve.json (the router's sigmoid, the two
norms `use_qk_norm` is read as, the rotary pairing, the MTP head left out).

    block      a = x + attn(RMSNorm(x));  out = a + ffn(RMSNorm(a))
    attention  q = RMSNorm_192(u W_q) a head = [q_nope 128 | q_rope 64], q_rope
               rotated;  [c_raw 512 | k_raw 64] = u W_kva;  c = RMSNorm(c_raw);
               k_rope = rotate(k_raw), one for all heads;  [k_nope | v] a head
               = c W_kvb (128 | 128);  softmax(scale [q_nope | q_rope] .
               [k_nope | k_rope]) v, causal;  W_o.  scale = 192^-0.5 m^2, m =
               0.1 mscale_all_dim ln(factor) + 1 (YaRN)
    cached     [c | k_rope]: 576 values a token and layer, nothing a head
    absorbed   W_kvb a head = [W_UK | W_UV]:  q' = W_UK q_nope (512);  score =
               scale [q' | q_rope] . [c | k_rope];  o = (sum P c) W_UV: the
               same numbers, and the cache is never up-projected
    ffn        layer < first_k_dense_replace: SwiGLU of `intermediate_size`;
               else shared(u) + sum_e w_e expert_e(u), s = sigmoid(u W_r),
               the top k of s + bias chosen, w_e = scale s_e / sum_chosen s;
               `experts_held = (first, count)`: this chip holds those
               experts and computes their part of the sum

Serving cache, per layer (`Decoder.init_cache`): one pool of the engine's
allocator's pages, a token's 576 values on 640 lanes. A decode step writes
its token's row and attends in the absorbed form (`ops/paged_attention.py`
`latent_attention`, on the TPU the kernel `mla_decode`: a page read once, as
keys and as values). A prefill attends in the published form over the call's own q, k, v (the flash
forward at keys of 192 and values of 128, `mla_flash`: no scores tensor) and
then writes the rows; it never reads a cached page, so the engine shares no
prefix for this family. A prefill's wave runs a row at a time (`_Row`): the
up-projected q, k, v of 16 x 4,096 tokens and the expert layer's rows laid
out by expert would be 4.3 GB each.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.initializers import kernel_init
from ray_tpu.models.layers import (Decoder, Mlp, SparseMoe, apply_rope,
                                   batch_positions, dense, embed,
                                   init_params, no_lora, norm, rope_freqs)
from ray_tpu.ops.attention import attention_reference, flash_attention
from ray_tpu.ops.paged_attention import latent_attention, latent_write

# Standard deviation of a seeded router's logits (its input has unit RMS).
# Not sharpened as the softmax families' are: under sigmoid scores divided by
# their sum the chosen eight weigh nearly alike whatever the deviation (at 1
# the eighth is 0.8 of the first, past 4 they saturate and all read 1.000), so
# a near-tie at the eighth place, which bf16 activations and a float32
# reference decide differently in some 3% of a layer's tokens, always swaps
# an eighth of the routed sum. On the chip the check's sound readings were
# 0.12-1.32 at 1 (24 seeds), 0.21-1.20 at 0.5, 0.11-1.19 at 2, 0.10-0.65 at 4
# and 0.10-0.43 at 8 (12 seeds each), against 0.64-1.86 in four significant
# bits: no limit stands at any, and from 4 up the bias decides among the
# saturated scores and the load is skewed (9 of 32 held experts touched by 16
# rows where an even router touches 20). What does stand is the deviation of
# what a near-tie swaps: `EXPERT_DOWN_STD` (PERF.md section 6, PR 48).
ROUTER_LOGIT_STD = 1.0
# Deviation of the seeded routed experts' down-projections, in lecun's: one
# over the routed scaling factor (0.4), so that the routed sum, whose weights
# add up to 2.5, has the RMS of a layer whose weights add up to one and does
# not drown the shared expert beside it. At lecun's (1.0) a swapped expert
# moves a token's residual by a seventh and the later layers' choices with
# it; at 0.4 the check's sound readings are 0.06-0.20 against 0.56-0.94 in
# four significant bits (12 seeds; the configuration's `check.why` has the
# full set). Routing, and with it every shape and byte the kernels see, does
# not depend on it.
EXPERT_DOWN_STD = 0.4


@dataclasses.dataclass(frozen=True)
class SarvamMlaConfig:
    vocab_size: int = 262_144
    hidden_size: int = 4096
    intermediate_size: int = 16_384       # the dense MLP of the first layers
    moe_intermediate_size: int = 2048     # one routed expert's width
    num_experts: int = 128                # the router's columns
    num_experts_per_tok: int = 8
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) on this chip
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    num_layers: int = 32
    num_heads: int = 64
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 10_000.0
    # `rope_scaling` (deepseek_yarn)
    yarn_factor: float = 40.0
    yarn_original_max_position_embeddings: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    max_seq_len: int = 131_072
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # (a list from a JSON file hashes as a tuple does)
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token's cache row holds: the latent, then the rotary
        key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def _mscale(self, m: float) -> float:
        """YaRN's `yarn_get_mscale(factor, m)`."""
        return 0.1 * m * math.log(self.yarn_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        return (self.q_head_dim ** -0.5
                * self._mscale(self.yarn_mscale_all_dim) ** 2)

    def rope(self) -> Tuple[jax.Array, float]:
        """(inverse frequencies [qk_rope_head_dim / 2], factor on cos and
        sin)."""
        return rope_freqs(self.qk_rope_head_dim, self.rope_theta, (
            self.yarn_factor, self.yarn_original_max_position_embeddings,
            self.yarn_beta_fast, self.yarn_beta_slow)), \
            self._mscale(self.yarn_mscale) / self._mscale(
                self.yarn_mscale_all_dim)

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "SarvamMlaConfig":
        """Test-sized: the dense layer and two expert layers, 16 experts
        top-4, float32, seconds on the CPU."""
        return SarvamMlaConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            experts_held=(0, 16), num_layers=3, num_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=32, yarn_original_max_position_embeddings=16,
            max_seq_len=512, dtype=jnp.float32, param_dtype=jnp.float32),
            **kw})


class LatentAttention(nn.Module):
    """Multi-head latent attention. Its four kernels are plain parameters
    (no `kernel` under the name): the absorbed form multiplies by slices of
    `kv_b_proj`, not by the projection."""
    cfg: SarvamMlaConfig
    flash: bool = False   # the published form through the flash kernel

    @nn.compact
    def __call__(self, x, positions, pages=None, paged=None):
        """`pages`: this layer's pool for a decode step, with `paged` =
        (page_table, write_mask, seq_lens): returns (out, pages). Without:
        the whole sequence in the published form over its own keys, and
        returns (out, the rows [B,S,576] a cache would hold of it)."""
        cfg = self.cfg
        b, s, hid = x.shape
        h, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rot, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        weight = lambda name, rows, cols: self.param(
            name, kernel_init, (rows, cols), cfg.param_dtype).astype(
                cfg.dtype)
        w_q = weight("q_proj", hid, h * cfg.q_head_dim)
        w_kva = weight("kv_a_proj_with_mqa", hid, cfg.latent_width)
        w_kvb = weight("kv_b_proj", rank, h * (nope + dv)).reshape(
            rank, h, nope + dv)
        w_o = weight("o_proj", h * dv, hid)
        freqs, factor = cfg.rope()
        rope = lambda t: apply_rope(t, positions, cfg.rope_theta, freqs,
                                    factor)
        q = norm(cfg, "q_norm")((x @ w_q).reshape(b, s, h, cfg.q_head_dim))
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
        ckr = x @ w_kva
        c = norm(cfg, "kv_a_layernorm")(ckr[..., :rank])
        k_rope = rope(ckr[..., None, rank:])                  # [B,S,1,rot]
        rows = jnp.concatenate([c, k_rope[:, :, 0]], axis=-1)  # [B,S,576]
        if pages is not None:
            # Absorbed: the step's row written, then every head's query
            # against the pool's rows as they lie.
            page_table, write_mask, seq_lens = paged
            pages = latent_write(pages, rows, page_table, positions,
                                 write_mask)
            q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0],
                               w_kvb[..., :nope])
            mixed = latent_attention(
                jnp.concatenate([q_abs, q_rope[:, 0]], axis=-1), pages,
                page_table, seq_lens, rank, cfg.softmax_scale)
            out = jnp.einsum("bhr,rhd->bhd", mixed, w_kvb[..., nope:])
            return out.reshape(b, 1, h * dv) @ w_o, pages
        kv = jnp.einsum("bsr,rhd->bshd", c, w_kvb)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rot))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        attend = flash_attention if self.flash else attention_reference
        # (the call's own keys: padding lies past every real query)
        out = attend(q, k, kv[..., nope:], causal=True,
                     scale=cfg.softmax_scale)
        return out.reshape(b, s, h * dv) @ w_o, rows


class SarvamMlaLayer(nn.Module):
    cfg: SarvamMlaConfig
    dense_mlp: bool
    flash: bool = False

    @nn.compact
    def __call__(self, x, positions, pages=None, paged=None):
        """-> (out, the layer's pool after a decode step, or without one the
        rows [B,S,576] its cache would hold)."""
        cfg = self.cfg
        mixed, kept = LatentAttention(cfg, self.flash, name="self_attn")(
            norm(cfg, "input_layernorm")(x), positions, pages, paged)
        x = x + mixed
        u = norm(cfg, "post_attention_layernorm")(x)
        if self.dense_mlp:
            return x + Mlp(cfg, name="mlp")(u), kept
        held = cfg.experts_held
        routed = SparseMoe(
            cfg, num_experts=cfg.num_experts,
            intermediate=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok, router_std=ROUTER_LOGIT_STD,
            held=None if held[1] == cfg.num_experts else held,
            scoring="sigmoid", scale=cfg.routed_scaling_factor,
            down_std=EXPERT_DOWN_STD, name="mlp")
        shared = Mlp(dataclasses.replace(
            cfg, intermediate_size=cfg.moe_intermediate_size
            * cfg.num_shared_experts), name="shared_experts")
        return x + routed(u) + shared(u), kept


class _Row(SarvamMlaLayer):
    """The layer over one row of a prefill's wave, as `nn.scan` calls it."""

    def __call__(self, carry, row):
        x, positions = row
        out, rows = SarvamMlaLayer.__call__(self, x[None], positions[None])
        return carry, (out[0], rows[0])


# A prefill's wave a row at a time, the parameters shared and each row's
# `expert_load` kept (the engine sums whatever it is handed).
_RowByRow = nn.scan(_Row, variable_broadcast="params",
                    variable_axes={"expert_load": 0},
                    split_rngs={"params": False})


class SarvamMlaModel(Decoder):
    cfg: SarvamMlaConfig

    # A prefill wants the head on a row's last position only (the logits of
    # a wave's 65,536 positions over 65,536 ids would be 17 GB).
    num_logits_to_keep = 1
    latent_width = property(lambda self: self.cfg.latent_width)

    @property
    def latent_layer_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.cfg.num_layers))

    @property
    def expert_layer_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.cfg.first_k_dense_replace,
                           self.cfg.num_layers))

    @nn.nowrap
    def _layer(self, i: int, cls=SarvamMlaLayer, **kw):
        return cls(self.cfg, i < self.cfg.first_k_dense_replace, **kw)

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg, [self._layer(i) for i in range(cfg.num_layers)],
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
        for i in range(cfg.num_layers):
            name = f"layers_{i}"
            if paged_kv is None:
                x, _ = self._layer(i, name=name)(x, positions)
            elif slots is None:
                x, pages = self._layer(i, name=name)(
                    x, positions, paged_kv[i], paged)
                new_caches.append(pages)
            else:
                _, (x, rows) = self._layer(i, _RowByRow, flash=True,
                                           name=name)(None, (x, positions))
                new_caches.append(latent_write(paged_kv[i], rows, page_table,
                                               positions, write_mask))
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = dense(cfg, cfg.vocab_size, "lm_head")(norm(cfg, "norm")(x))
        if paged_kv is not None:
            return logits, new_caches
        return logits
