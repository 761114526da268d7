"""Seeded weights for families whose checkpoints are bf16: drawn in float32
and rounded, as a checkpoint's weights are, block of rows by block of rows
(`models/olmo_hybrid.py`, `models/sdar_moe.py`)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Elements one compiled random generator fills at once: the TPU compiler's
# time for a generator grows with its array (the embedding whole: 11 s; in
# blocks of this size under one small loop: 1 s; compile, PR 29).
INIT_BLOCK = 1 << 22


def in_blocks(key, shape, draw):
    """A 2-D array of `shape` from `draw(key, block_shape)`, block of rows by
    block of rows under one small loop."""
    rows, cols = shape
    fit = max(1, INIT_BLOCK // cols)
    n = min((d for d in range(1, rows + 1)
             if rows % d == 0 and rows // d <= fit), default=rows)
    return jax.lax.map(lambda k: draw(k, (rows // n, cols)),
                       jax.random.split(key, n)).reshape(shape)


def kernel_init(key, shape, dtype, fan_in: Optional[int] = None):
    """A projection [fan_in, features]: flax's Dense default (lecun normal:
    truncated at two standard deviations, variance 1 / fan_in), drawn in
    float32 and rounded to `dtype` as a checkpoint's weights are, in blocks.
    Not drawn in bf16 itself: that draw's uniform has seven bits and a mean
    of 127/256, so every matrix gets a mean of -0.018 standard deviations,
    and 16 layers deep nine tenths of the residual stream is one constant
    vector whatever the prompt (PERF.md section 6, PR 29). `fan_in` where it
    is not the rows (several kernels stacked along them)."""
    std = (fan_in or shape[0]) ** -0.5 / 0.87962566103423978
    return in_blocks(key, shape, lambda k, block: (
        std * jax.random.truncated_normal(k, -2.0, 2.0, block, jnp.float32)
    ).astype(dtype))


def embed_init(key, shape, dtype):
    """The embedding [vocabulary, hidden]: normal, rows of unit expected
    norm."""
    std = shape[1] ** -0.5
    return in_blocks(key, shape, lambda k, block: (
        std * jax.random.normal(k, block, jnp.float32)).astype(dtype))
