"""Llama-family transformer in flax.linen — the flagship model.

TPU-first design (net-new; the reference delegates modeling to torch/vLLM):
- bfloat16 activations, fp32 RMSNorm accumulation, RoPE, GQA, SwiGLU;
- every einsum is laid out for the MXU (last dims multiples of 128);
- sharding via logical-axis annotations resolved by
  ray_tpu.parallel.sharding.ParamShardingRules (DP/FSDP/TP/SP on one mesh);
- attention dispatches to the Pallas flash kernel on a single seq shard or
  ring attention when the mesh has a "seq" axis;
- serving reads and writes the paged K/V pool through ops/paged_attention.

Config presets mirror the sizes users run on the reference stack (BASELINE
config 2/4 uses Llama-3-8B).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ray_tpu.models.layers import Decoder, RMSNorm, apply_rope
from ray_tpu.ops.attention import (
    FLASH_KEPT,
    attention_reference,
    flash_attention,
)
from ray_tpu.ops.paged_attention import init_kv_pages, paged_write_attend
from ray_tpu.parallel.sharding import ParamShardingRules


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # "flash" (pallas), "reference", or "ring" (sequence parallel)
    attention_impl: str = "flash"
    remat: bool = True
    # >0 replaces the dense SwiGLU Mlp with a switch-routed MoE of this many
    # experts (expert dim shards over the mesh "expert" axis — EP).
    num_experts: int = 0
    moe_capacity_factor: float = 1.25

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(hidden_size=8192, intermediate_size=28672,
                           num_layers=80, num_heads=64, num_kv_heads=8)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """Test-sized config: runs on a CPU mesh in seconds."""
        return LlamaConfig(vocab_size=vocab_size, hidden_size=128,
                           intermediate_size=256, num_layers=2, num_heads=4,
                           num_kv_heads=2, head_dim=32, max_seq_len=512,
                           dtype=jnp.float32, attention_impl="reference",
                           remat=False)


# Parameter sharding rules: path regex → logical axes (resolved against the
# mesh by ParamShardingRules; tensor axis shards heads/mlp, fsdp shards the
# remaining embed dim — the megatron + ZeRO-3 combination).
LLAMA_SHARDING = ParamShardingRules([
    (r"embed_tokens/embedding", ("vocab", "embed_fsdp")),
    (r"(q_proj|k_proj|v_proj)/kernel", ("embed_fsdp", "heads", "head_dim")),
    (r"o_proj/kernel", ("heads", "head_dim", "embed_fsdp")),
    (r"(gate_proj|up_proj)/kernel", ("embed_fsdp", "mlp")),
    (r"down_proj/kernel", ("mlp", "embed_fsdp")),
    # MoE experts: the leading expert dim shards over the "expert" mesh
    # axis (EP); within an expert the FFN shards like the dense Mlp.
    (r"router/kernel", ("embed", None)),
    (r"(gate_kernel|up_kernel)", ("expert", "embed_fsdp", "mlp")),
    (r"down_kernel", ("expert", "mlp", "embed_fsdp")),
    (r"lm_head/kernel", ("embed_fsdp", "vocab")),
    (r"norm|input_layernorm|post_attention_layernorm", ("embed",)),
])


# What a remat'd layer keeps for its backward beside its input: 28 KB a token
# at Mistral's widths against the 8 KB of the input alone. The wide FFN
# products (28 KB a token each) have no name and are recomputed.
KEPT = ("attn_q", "attn_k", "attn_v", "attn_proj") + FLASH_KEPT


def lora_delta(x, bank, idx):
    """Per-sequence batched LoRA (the TPU-native multi-adapter form —
    reference: ray.llm's LoRA multiplex deployments delegate this to
    vLLM's punica kernels; here it is two gathered einsums the MXU eats
    directly). bank = {"a": [K, r, Din], "b": [K, Dout, r], "scale"};
    idx [B] selects each sequence's adapter (slot 0 = zero adapter)."""
    a_sel = jnp.take(bank["a"], idx, axis=0)  # [B, r, Din]
    b_sel = jnp.take(bank["b"], idx, axis=0)  # [B, Dout, r]
    h1 = jnp.einsum("bsd,brd->bsr", x.astype(jnp.float32),
                    a_sel.astype(jnp.float32))
    out = jnp.einsum("bsr,bor->bso", h1, b_sel.astype(jnp.float32))
    scale = bank.get("scale", 1.0)
    if jnp.ndim(scale) == 1:  # per-slot scales
        scale = jnp.take(scale, idx)[:, None, None]
    return out * scale


class Attention(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None, lora=None,
                 lora_idx=None):
        """`kv_pages`: this layer's (k_pages, v_pages) when serving, with
        `paged` = (page_table, write_mask, seq_lens); None for the whole
        sequence without a cache."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        q = dense((h, d), "q_proj")(x)
        k = dense((hk, d), "k_proj")(x)
        v = dense((hk, d), "v_proj")(x)
        if lora is not None:
            if "q_proj" in lora:
                q = q + lora_delta(x, lora["q_proj"], lora_idx).reshape(
                    b, s, h, d).astype(q.dtype)
            if "k_proj" in lora:
                k = k + lora_delta(x, lora["k_proj"], lora_idx).reshape(
                    b, s, hk, d).astype(k.dtype)
            if "v_proj" in lora:
                v = v + lora_delta(x, lora["v_proj"], lora_idx).reshape(
                    b, s, hk, d).astype(v.dtype)

        def o_proj(out4d):
            y = nn.DenseGeneral(
                cfg.hidden_size, axis=(-2, -1), use_bias=False,
                dtype=cfg.dtype, param_dtype=jnp.float32, name="o_proj")(
                    out4d)
            if lora is not None and "o_proj" in lora:
                flat = out4d.reshape(b, s, h * d)
                y = y + lora_delta(flat, lora["o_proj"],
                                   lora_idx).astype(y.dtype)
            return y

        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

        if kv_pages is not None:
            page_table, write_mask, seq_lens = paged
            pos2d = positions if positions.ndim == 2 else jnp.broadcast_to(
                positions[None, :], (b, s))
            out, kv_pages = paged_write_attend(
                q, k, v, kv_pages, page_table, pos2d, write_mask, seq_lens,
                mesh=self.mesh)
            return o_proj(out), kv_pages

        # The narrow values a remat'd layer keeps (`KEPT`): q, k, v as
        # rotated, k and v at their KV heads, and below the projection.
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        if cfg.attention_impl == "ring" and self.mesh is not None:
            from ray_tpu.parallel.ring import ring_attention

            out = ring_attention(q, k, v, mesh=self.mesh, causal=True)
        elif cfg.attention_impl == "flash":
            out = flash_attention(q, k, v, causal=True, mesh=self.mesh)
        else:
            out = attention_reference(q, k, v, causal=True)
        return checkpoint_name(o_proj(out), "attn_proj"), None


class Mlp(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
            name=name)
        gate = dense(cfg.intermediate_size, "gate_proj")(x)
        up = dense(cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, kv_pages=None, paged=None, lora=None,
                 lora_idx=None):
        cfg = self.cfg
        attn_out, new_cache = Attention(cfg, self.mesh, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x),
            positions, kv_pages, paged, lora, lora_idx)
        x = x + attn_out
        if cfg.num_experts > 0:
            from ray_tpu.models.moe import MoEMlp

            mlp = MoEMlp(cfg.hidden_size, cfg.intermediate_size,
                         cfg.num_experts,
                         capacity_factor=cfg.moe_capacity_factor,
                         dtype=cfg.dtype, name="mlp")
        else:
            mlp = Mlp(cfg, name="mlp")
        x = x + mlp(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x))
        return x, new_cache


class LlamaModel(Decoder):
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    def init_cache(self, cache_cfg, mesh=None):
        """The serving engine's cache, per layer: (k_pages, v_pages)."""
        cfg = self.cfg
        return [init_kv_pages(cache_cfg, cfg.num_kv_heads, cfg.head_dim,
                              cfg.dtype, mesh=mesh)
                for _ in range(cfg.num_layers)]

    @nn.compact
    def __call__(self, input_ids, positions=None, paged_kv=None,
                 page_table=None, write_mask=None, seq_lens=None, lora=None,
                 lora_idx=None, slots=None):
        """lora: {"layers_<i>": {proj: {"a": [K,r,Din], "b": [K,Dout,r],
        "scale": s}}} adapter BANKS (runtime jit args, not flax params —
        adapter loads update values without recompiling); lora_idx [B]
        picks each sequence's adapter, slot 0 = none. `slots` (the engine
        slots a prefill fills) is for models with a state per slot: K/V
        pages are addressed through `page_table` alone."""
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed_tokens")(input_ids)
        layer_cls = DecoderLayer
        if cfg.remat and paged_kv is None:
            layer_cls = nn.remat(
                DecoderLayer, static_argnums=(),
                policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
        paged = (page_table, write_mask, seq_lens)
        new_caches = []
        for i in range(cfg.num_layers):
            kv_pages = paged_kv[i] if paged_kv is not None else None
            layer_lora = (lora or {}).get(f"layers_{i}")
            x, new_cache = layer_cls(cfg, self.mesh, name=f"layers_{i}")(
                x, positions, kv_pages, paged, layer_lora, lora_idx)
            new_caches.append(new_cache)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="lm_head")(x)
        if paged_kv is not None:
            return logits, new_caches
        return logits


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
