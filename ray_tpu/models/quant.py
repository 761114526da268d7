"""Int8 weight quantization for serving (reference: the reference serves
8B+ models through vLLM's quantized kernels; here quantization is a pytree
transform + an in-jit dequant hook on the engine).

Scheme: per-output-channel absmax int8 for every matrix-shaped parameter
(attention/MLP kernels, embeddings); vectors (norms, biases) stay bf16.
Quantized leaves are `{"__q__": int8[..], "s": bf16 scale}` dicts; the
whole tree lives in HBM at ~1 byte/param. `dequantize_tree` runs INSIDE
the jitted step (LLMEngine's `param_transform`), so XLA fuses the
int8→bf16 converts into the consuming matmuls and the full-precision
weights never exist as a resident tree.

This is the single-chip path toward 8B-class models on a 16 GiB v5e:
bf16 8B weights alone exceed HBM; int8 weights (+ paged KV) fit.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


def _is_qleaf(x: Any) -> bool:
    return isinstance(x, dict) and "__q__" in x


def quantize_tree(params: Any, min_size: int = 4096) -> Any:
    """Quantize matrix-shaped leaves of a real param tree."""

    def q(x):
        if getattr(x, "ndim", 0) >= 2 and x.size >= min_size:
            xf = x.astype(jnp.float32)
            scale = jnp.max(jnp.abs(xf), axis=tuple(range(x.ndim - 1)),
                            keepdims=True) / 127.0
            scale = jnp.maximum(scale, 1e-8)
            qx = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
            return {"__q__": qx, "s": scale.astype(jnp.bfloat16)}
        return x

    return jax.tree.map(q, params)


def dequantize_tree(qparams: Any, dtype=jnp.bfloat16) -> Any:
    """In-jit inverse: int8 * scale → dtype. XLA fuses the converts into
    the consuming dots, so this does not materialize a resident bf16
    tree."""

    def dq(x):
        if _is_qleaf(x):
            return (x["__q__"].astype(dtype) * x["s"].astype(dtype))
        return x

    return jax.tree.map(dq, qparams, is_leaf=_is_qleaf)


def random_quantized_like(params_shape: Any, *, seed: int = 0,
                          scale: float = 0.02, min_size: int = 4096) -> Any:
    """Build an int8 tree DIRECTLY from a jax.eval_shape param skeleton —
    so a full-precision tree never has to exist (an 8B bf16 init would
    itself overflow a 16 GiB chip). One jitted dispatch builds the whole
    tree (one program instead of a dispatch per leaf).
    Benchmark/testing helper; real checkpoints go through quantize_tree."""
    leaves, treedef = jax.tree_util.tree_flatten(params_shape)

    def build():
        out = []
        for i, leaf in enumerate(leaves):
            if len(leaf.shape) >= 2 and math.prod(leaf.shape) >= min_size:
                # Cheap deterministic pseudo-noise (iota hash) — throughput
                # benches don't need statistical quality, and fold_in/
                # randint per leaf dominates build time at 8B scale.
                flat = jnp.arange(math.prod(leaf.shape), dtype=jnp.int32)
                qx = ((flat * (1103515245 + i) + 12345) % 255 - 127
                      ).astype(jnp.int8).reshape(leaf.shape)
                s_shape = (tuple(1 for _ in leaf.shape[:-1])
                           + (leaf.shape[-1],))
                out.append({"__q__": qx,
                            "s": jnp.full(s_shape, scale / 127.0,
                                          jnp.bfloat16)})
            else:
                out.append(jnp.ones(leaf.shape, jnp.bfloat16))
        return out

    out = jax.jit(build)()
    return jax.tree_util.tree_unflatten(treedef, out)


def quantized_bytes(qparams: Any) -> int:
    """Resident HBM bytes of a quantized tree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(qparams):
        total += leaf.size * leaf.dtype.itemsize
    return total
