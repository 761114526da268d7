"""Nemotron-H (`model_type` `nemotron_h`, Nemotron 3 Super's): a decoder
whose every block is ONE mixer under one norm and one residual, and the
mixer is of three kinds (`hybrid_override_pattern`, a letter a block): `M` a
Mamba-2 (SSD) mixer whose B and C come in `n_groups` groups, `*` grouped-query
attention without positions, `E` a sigmoid-routed mixture of experts that
work in a latent narrower than the hidden state, beside a shared expert; no
expert has a gate: down(relu(up x)^2).

Follows huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's
config.json; parameter names are HF's where HF has one, the experts of a
block held as two stacks. What the config does not state is listed under
`assumed` in benchmark/configs/nemotron-3-super-120b-a12b-serve.json (no
positional encoding in attention, the latent reading of the experts, the
multi-token-prediction module left out).

    block i     x + mixer_i(RMSNorm(x)), the kind pattern[i]; a final RMSNorm;
                an untied head, on a prefill's last position only
    M           `layers.Mamba2Mixer` with `mamba_n_groups` groups of B and C
                (head h reads group h // (heads / groups); the gated norm
                within each group's channels)
    *           num_heads query heads over num_kv_heads K/V heads, no bias, no
                positional encoding, softmax(head_dim^-0.5 q k^T), causal
    E           s = sigmoid(u W_r) over all `num_experts`, float32; the top k
                of s + bias chosen, w_e = scale s_e / sum_chosen s;
                l = u W_fc1 (hidden -> latent, no bias, no activation);
                y = (sum_e w_e down_e(relu(up_e l)^2)) W_fc2 + shared(u),
                shared(u) = down(relu(up u)^2) on the hidden state;
                `experts_held = (first, count)`: this chip holds those
                experts and computes their part of the sum (expert
                parallelism's share; the rest is the peers'), both latent
                projections, the router and the shared expert whole

Serving cache, per layer (`Decoder.init_cache`): an `M` block holds, per
engine slot, the last three inputs of its convolution [3, d_inner + 2 G N]
and the float32 state [heads, d_head, N], the N states on the lanes; a `*`
block paged K/V; an `E` block NOTHING: its entry is `()`
(`cacheless_layer_ids`), in and out of both programs.
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

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"

# Standard deviation of a seeded router's logits (its input has unit RMS):
# not sharpened, as models/sarvam_mla.py's is not and for its reason (under
# sigmoid scores divided by their sum the chosen weigh nearly alike whatever
# the deviation). What a near-tie at the last place swaps is one expert of
# `num_experts_per_tok`: a twenty-second of the routed sum here.
ROUTER_LOGIT_STD = 1.0
# Deviation of the seeded routed experts' down-projections, in lecun's: half
# of one over the routed scaling factor (5). Sarvam's rule (one over the
# factor: 0.2 here, so that the routed sum, whose weights add up to 5, has
# the RMS of a layer whose weights add up to one beside the shared expert)
# leaves the check's limit no room: among 512 sigmoid scores the 22nd and
# the 23rd lie some 0.02 apart in the logit, bf16 activations and a float32
# reference choose differently in most tokens of a block, and every such
# swap moves one of the five or six chosen experts this chip holds. What the
# check reads of a sound run and of a fault planted in the routed path both
# grow with this value, the fault's the faster while bf16's own rounding
# (0.02-0.05) is the floor: 0.1 is the largest at which sound runs keep
# their room under the limit AND a zeroed routed sum, the wrong share of the
# experts and four significant bits (control A) are each refused at every
# seed (at 0.05 the zeroed sum passed at a third of the seeds; at 0.15 and
# 0.2 sound runs reach 0.09 and 0.13). The readings are in PERF.md section 6
# (PR 56) and in the configuration's `check.why`. Routing, and with it every
# shape and byte the kernels see, does not depend on it.
EXPERT_DOWN_STD = 0.1


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    hidden_size: int = 4096
    # One letter a block (`M`, `*` or `E`): the published 88.
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    num_experts: int = 512                # the router's columns
    num_experts_per_tok: int = 22
    experts_held: Tuple[int, int] = (0, 512)   # (first, count) on this chip
    moe_intermediate_size: int = 2688     # one routed expert's width
    moe_latent_size: int = 1024           # the rows the experts take
    shared_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    max_seq_len: int = 262_144
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # (a list from a JSON file hashes as a tuple does)
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.hybrid_override_pattern) - {MAMBA, ATTENTION, EXPERTS}
        if bad or not self.hybrid_override_pattern:
            raise ValueError(
                f"hybrid_override_pattern holds {sorted(bad) or 'nothing'}")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} heads in "
                             f"{self.mamba_n_groups} groups")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, then the groups' B, then
        their C."""
        return (self.mamba_n_heads * self.mamba_d_head
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "NemotronHConfig":
        """Test-sized: five blocks with each kind among them, 8 experts
        top-3 in a latent of 32, two groups of B and C, float32, seconds on
        the CPU."""
        return NemotronHConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64,
            hybrid_override_pattern="ME*EM", num_heads=4, num_kv_heads=2,
            head_dim=16, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_n_groups=2, mamba_chunk_size=16, num_experts=8,
            num_experts_per_tok=3, experts_held=(0, 8),
            moe_intermediate_size=16, moe_latent_size=32,
            shared_intermediate_size=48, max_seq_len=512,
            dtype=jnp.float32, param_dtype=jnp.float32), **kw})


def relu2(x):
    return jnp.square(nn.relu(x))


class Attention(nn.Module):
    """Causal softmax attention, `num_heads` query heads over `num_kv_heads`
    K/V heads, no bias, no positional encoding."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None):
        """`kv_pages`: this block's (k_pages, v_pages) when serving, with
        `paged` = (page_table, write_mask, seq_lens); None for the whole
        sequence without a cache."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        scale = d ** -0.5
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
                # A prefill's rows one at a time (one row's float32 scores
                # at a time, whatever the wave and the bucket).
                out = jax.lax.map(
                    lambda row: attend(*(t[None] for t in row))[0],
                    (q, page_table, positions, seq_lens))
        return dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, s, h * d)), kv_pages


class SharedMlp(nn.Module):
    """The shared expert: down(relu(up x)^2), every token whole."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        up = dense(cfg, cfg.shared_intermediate_size, "up_proj")(x)
        return dense(cfg, cfg.hidden_size, "down_proj")(relu2(up))


class LatentMoe(nn.Module):
    """The routed experts in the latent, beside the shared expert on the
    hidden state."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        held = cfg.experts_held
        latent = dense(cfg, cfg.moe_latent_size, "fc1_latent_proj")(u)
        routed = SparseMoe(
            cfg, num_experts=cfg.num_experts,
            intermediate=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok, router_std=ROUTER_LOGIT_STD,
            held=None if held[1] == cfg.num_experts else held,
            scoring="sigmoid", scale=cfg.routed_scaling_factor,
            down_std=EXPERT_DOWN_STD, act="relu2", name="experts")(u, latent)
        return (dense(cfg, cfg.hidden_size, "fc2_latent_proj")(routed)
                + SharedMlp(cfg, name="shared_experts")(u))


class NemotronHBlock(nn.Module):
    """x + mixer(RMSNorm(x)): one mixer of `kind`, one norm, one residual."""
    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, mask=None, cache=None, paged=None,
                 rows=None):
        cfg = self.cfg
        normed = norm(cfg, "norm")(x)
        if self.kind == MAMBA:
            mixed, cache = Mamba2Mixer(cfg, cfg.mamba_n_groups, name="mixer")(
                normed, mask, cache, rows)
        elif self.kind == ATTENTION:
            mixed, cache = Attention(cfg, name="mixer")(
                normed, positions, cache, paged)
        else:
            mixed = LatentMoe(cfg, name="mixer")(normed)
        return x + mixed.astype(cfg.dtype), cache


class NemotronHModel(Decoder):
    cfg: NemotronHConfig

    # A prefill wants the head on a row's last position only, as published
    # (`num_logits_to_keep` 1).
    num_logits_to_keep = 1

    @nn.nowrap
    def _blocks_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.cfg.hybrid_override_pattern)
                     if k == kind)

    state_layer_ids = property(lambda self: self._blocks_of(MAMBA))
    expert_layer_ids = property(lambda self: self._blocks_of(EXPERTS))
    # An expert block keeps nothing between steps.
    cacheless_layer_ids = expert_layer_ids

    def init_cache(self, cache_cfg, mesh=None):
        """Per block: (conv_tail [max_seqs, 3, d_inner + 2 G N], S
        [max_seqs, heads, d_head, N] float32) on `M`, a row per engine slot,
        the states minor; (k_pages, v_pages) on `*`; `()` on `E`."""
        cfg = self.cfg
        return super().init_cache(
            cache_cfg, mesh, tail=(cfg.mamba_d_conv - 1, cfg.conv_dim),
            state=(cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state))

    @nn.nowrap
    def init_params(self, rng):
        cfg = self.cfg
        return init_params(
            rng, cfg,
            [NemotronHBlock(cfg, k) for k in cfg.hybrid_override_pattern],
            {"norm_f": norm(cfg, None),
             "lm_head": dense(cfg, cfg.vocab_size, None)})

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
        x = embed(cfg, "embed_tokens")(input_ids)
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            cache = paged_kv[i] if paged_kv is not None else None
            x, new_cache = NemotronHBlock(cfg, kind, name=f"layers_{i}")(
                x, positions, write_mask, cache, paged, slots)
            new_caches.append(new_cache)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = dense(cfg, cfg.vocab_size, "lm_head")(
            norm(cfg, "norm_f")(x))
        if paged_kv is not None:
            return logits, new_caches
        return logits
