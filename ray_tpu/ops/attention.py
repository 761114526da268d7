"""Attention ops: numerically-stable blockwise (flash) attention.

Net-new TPU kernel work (the reference free-rides on vLLM's CUDA kernels —
SURVEY §7.3): a Pallas TPU flash-attention kernel for the hot path plus a pure
jnp blockwise reference used on CPU meshes, in tests, and as the per-step
primitive of ring attention (ray_tpu/parallel/ring.py).

Shapes follow jax convention: q [B, Sq, H, D], k/v [B, Skv, Hkv, D] with GQA
(H a multiple of Hkv).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.sharding import Mesh

NEG_INF = -1e30
# The names a remat policy may keep of the flash kernel's forward: its output
# and its row sums, so that a recomputation calls no `flash_fwd`.
FLASH_KEPT = ("flash_out", "flash_lse")


def _gqa_expand(k: jax.Array, v: jax.Array, num_heads: int) -> Tuple[jax.Array, jax.Array]:
    num_kv = k.shape[2]
    if num_kv == num_heads:
        return k, v
    rep = num_heads // num_kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    return k, v


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention (test oracle). `window`: a query sees the
    `window` keys up to and including its own position."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    k, v = _gqa_expand(k, v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST) * scale
    if causal:
        q_ids = jnp.arange(q.shape[1])[:, None] + q_offset
        k_ids = jnp.arange(k.shape[1])[None, :]
        visible = k_ids <= q_ids
        if window is not None:
            visible &= q_ids - k_ids < window
        logits = jnp.where(visible, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise primitive: one (q_block × kv_block) flash update. Shared by ring
# attention; operates on [B, S, H, D] blocks with running stats.
# ---------------------------------------------------------------------------
def block_attn_update(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, H, D] (already GQA-expanded)
    v: jax.Array,
    m: jax.Array,  # [B, H, Sq] running rowmax
    l: jax.Array,  # [B, H, Sq] running denominator
    o: jax.Array,  # [B, Sq, H, D] running numerator (unnormalized)
    *,
    scale: float,
    mask: Optional[jax.Array] = None,  # [Sq, Sk] additive (0 / NEG_INF)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * scale
    if mask is not None:
        s = s + mask[None, None, :, :]
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def block_attn_init(q: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, sq, h, d = q.shape
    m = jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, h, sq), dtype=jnp.float32)
    o = jnp.zeros((b, sq, h, d), dtype=jnp.float32)
    return m, l, o


def block_attn_finish(l: jax.Array, o: jax.Array, dtype) -> jax.Array:
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash attention kernel
# ---------------------------------------------------------------------------
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        # Feed the MXU native-dtype operands (bf16 in, fp32 accumulate via
        # preferred_element_type) — upcasting to f32 + HIGHEST precision would
        # run the MXU in multi-pass mode and dominate the kernel time.
        q = q_ref[0, 0]                            # [block_q, d]
        k = k_ref[0, 0]                            # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_ids <= q_ids, s, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=-1)
        m_scr[:, 0] = m_new
        v = v_ref[0, 0]
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Skip fully-masked kv blocks (upper triangle).
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)
        # Row logsumexp — the residual the backward kernels rebuild p from.
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(denom))[:, None]


def _flash_fwd_core(qt, kt, vt, cfg):
    """Forward on [B,H,S,D] layout. Returns (out, lse). The values may have
    a width of their own (latent attention's keys are 192 wide, its values
    128): the call is then named `mla_flash`, so a trace tells the two
    apart."""
    causal, scale, block_q, block_k, interpret = cfg
    b, h, sq, d = qt.shape
    dv = vt.shape[3]
    skv = kt.shape[2]
    num_k_blocks = skv // block_k
    grid = (b, h, sq // block_q, num_k_blocks)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=num_k_blocks)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), qt.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, 1)),
            _vmem((block_q, 1)),
            _vmem((block_q, dv)),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd" if dv == d else "mla_flash",
    )(qt, kt, vt)
    return out, lse


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     dq_scr, *, scale, causal, block_q, block_k,
                     num_k_blocks):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_ids <= q_ids, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])
        do = do_ref[0, 0]
        # dp = dO @ V^T
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                      block_q, block_k, num_q_blocks):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_ids <= q_ids, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])  # [bq, bk]
        do = do_ref[0, 0]
        pb = p.astype(do.dtype)
        # dV += P^T @ dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0]) * scale).astype(q.dtype)
        # dK += dS^T @ Q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_core(qt, kt, vt, out, lse, dout, cfg):
    causal, scale, block_q, block_k, interpret = cfg
    b, h, sq, d = qt.shape
    skv = kt.shape[2]
    num_q_blocks = sq // block_q
    num_k_blocks = skv // block_k
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,Sq,1]

    qkv_spec = lambda which: {
        "q": pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        "k": pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
    }[which]
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_k_blocks=num_k_blocks),
        grid=(b, h, num_q_blocks, num_k_blocks),
        in_specs=[qkv_spec("q"), qkv_spec("k"), qkv_spec("k"),
                  qkv_spec("q"), row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
        scratch_shapes=[_vmem((block_q, d))],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dout, lse, delta)

    # dk/dv: grid iterates q blocks sequentially per k block.
    qspec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    kspec = pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    rspec = pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_q_blocks=num_q_blocks),
        grid=(b, h, num_k_blocks, num_q_blocks),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((b, h, skv, d), kt.dtype),
                   jax.ShapeDtypeStruct((b, h, skv, d), vt.dtype)],
        scratch_shapes=[_vmem((block_k, d)), _vmem((block_k, d))],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dout, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(qt, kt, vt, cfg):
    out, _ = _flash_fwd_core(qt, kt, vt, cfg)
    return out


def _flash_core_fwd(qt, kt, vt, cfg):
    out, lse = _flash_fwd_core(qt, kt, vt, cfg)
    out = checkpoint_name(out, "flash_out")
    # Kept as [B, H, S]: the kernel's [B, H, S, 1] lies in HBM with its one
    # lane padded to 128.
    lse = checkpoint_name(lse[..., 0], "flash_lse")[..., None]
    return out, (qt, kt, vt, out, lse)


def _flash_core_bwd(cfg, res, dout):
    qt, kt, vt, out, lse = res
    return _flash_bwd_core(qt, kt, vt, out, lse, dout, cfg)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Pallas flash attention. q [B,Sq,H,D], k/v [B,Skv,Hkv,D] → [B,Sq,H,D].
    v may be [B,Skv,Hkv,Dv] of another width (→ [B,Sq,H,Dv], the kernel
    `mla_flash`): forward only, no scores tensor and no padding of v to D.

    Differentiable: forward saves per-row logsumexp, backward runs two Pallas
    kernels (dq with k sequential; dk/dv with q sequential) — the
    FlashAttention-2 recipe, O(S) memory. GQA expansion happens outside the
    custom_vjp so XLA differentiates the repeat into a segment-sum.

    Precision: MXU dots run at native input precision with f32 accumulation
    (the standard TPU flash tradeoff). f32 inputs are truncated to bf16 on
    the MXU; use attention_reference for full-f32 logits.

    Grid (B, H, q_blocks, k_blocks); the trailing dimension is sequential
    ("arbitrary") carrying running softmax stats in VMEM scratch.

    With a multi-device `mesh` the kernel runs under shard_map — batch over
    (data, fsdp), heads over tensor — because GSPMD cannot partition a
    Mosaic kernel; attention is independent per (batch, head), so no
    collective is needed inside.
    """
    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel.sharding import spec_for_shape

        q_spec = spec_for_shape(("batch", None, "heads", None), q.shape, mesh)
        kv_spec = spec_for_shape(("batch", None, "kv_heads", None), k.shape,
                                 mesh)
        if kv_spec != q_spec:
            # The tensor axis divides the query heads but not the KV heads:
            # give every query head its own KV head before splitting.
            k, v = _gqa_expand(k, v, q.shape[2])
            kv_spec = q_spec
        local = functools.partial(
            flash_attention, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret)
        return jax.shard_map(
            local, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
            out_specs=q_spec, check_vma=False)(q, k, v)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k, v = _gqa_expand(k, v, h)
    # Shrink blocks to divide the sequence (defaults are sized for long
    # power-of-two sequences; a 1536-long sequence steps down to 512/…).
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    while block_q > 1 and sq % block_q:
        block_q //= 2
    while block_k > 1 and skv % block_k:
        block_k //= 2
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq lens ({sq},{skv}) must divide blocks "
                         f"({block_q},{block_k})")
    # Layout [B, H, S, D] for clean 2D blocks.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    cfg = (causal, scale, block_q, block_k, interpret)
    if v.shape[-1] != d:
        # (the backward kernels take one width)
        out, _ = _flash_fwd_core(qt, kt, vt, cfg)
    else:
        out = _flash_core(qt, kt, vt, cfg)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Sliding-window (banded causal) forward: a prefill over the call's own keys
# ---------------------------------------------------------------------------
def _swa_flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale: float, window: int, block: int, band: int):
    """Grid (B, H, q blocks, band): step j of q block qi holds key block
    qi - (band - 1) + j, the band's blocks left to right and the diagonal
    block last; a block before the sequence's start is passed over (its
    index map repeats block 0, so nothing is fetched for it). A row whose
    keys in a block are all outside the band adds weights at m = NEG_INF
    that the diagonal block, where every row sees its own key, scales to 0."""
    qi = pl.program_id(2)
    j = pl.program_id(3)
    ki = qi - (band - 1) + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki >= 0)
    def _compute():
        q = q_ref[0, 0]                            # [block, d]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_ids = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_ids = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        s = jnp.where((k_ids <= q_ids) & (q_ids - k_ids < window), s,
                      NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=-1)
        m_scr[:, 0] = m_new
        v = v_ref[0, 0]
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == band - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


def sliding_window_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    scale: Optional[float] = None,
    block: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention in which query i sees keys i - window < j <= i, over
    the call's own q [B,S,H,D] and k/v [B,S,Hkv,D] (the Pallas kernel
    `swa_flash`; forward only). Key blocks wholly outside the band are not
    visited: a q block walks ceil((window - 1) / block) + 1 key blocks
    whatever S is, so the work grows with S x window and there is no scores
    tensor. K and V keep their Hkv heads: a query head's index map reads its
    group's."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    block = min(block, s)
    while block > 1 and s % block:
        block //= 2
    band = -(-(window - 1) // block) + 1
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    q_spec = pl.BlockSpec((1, 1, block, d),
                          lambda bi, hi, qi, j: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block, d), lambda bi, hi, qi, j: (
            bi, hi // rep, jnp.maximum(qi - (band - 1) + j, 0), 0))
    out = pl.pallas_call(
        functools.partial(_swa_flash_kernel, scale=scale, window=window,
                          block=block, band=band),
        grid=(b, h, s // block, band),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[_vmem((block, 1)), _vmem((block, 1)),
                        _vmem((block, d))],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="swa_flash",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# Learned block-sparse attention (InfLLM-V2, arXiv:2506.07900): a query past
# `dense_len` attends to `topk` key blocks it chooses by compressed keys
# ---------------------------------------------------------------------------
class SparseSizes(NamedTuple):
    """The selection's sizes (MiniCPM4's `sparse_config`). A compressed key
    is the mean of `kernel_size` keys, one every `kernel_stride`; a query
    chooses `topk` blocks of `block_size` keys, the first `init_blocks` and
    those of the last `window_size` keys among them; a query that sees fewer
    than `dense_len` keys attends to all of them."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def check(self) -> "SparseSizes":
        if (self.kernel_size != 2 * self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.window_size % self.block_size
                or self.dense_len % self.block_size):
            raise ValueError(
                f"{self}: a compressed key is two segments of kernel_stride, "
                "and blocks are whole segments")
        return self

    @property
    def per_block(self) -> int:
        """Segments of `kernel_stride` keys (and compressed keys that begin)
        in a block."""
        return self.block_size // self.kernel_stride

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size


def compressed_scores(q, means, t, sizes: SparseSizes, scale: float):
    """What a KV head's group of query heads makes of the compressed keys.
    q [..., G, Q, D] (G heads of the group, Q queries), means [..., N, D] the
    segment means (float32), t [..., Q] the queries' positions -> r
    [..., Q, N] float32: the sum over the group's heads of each head's softmax
    over the compressed keys c_i = (m_i + m_(i+1)) / 2 it sees (those with
    16 i + 31 <= t), -1 where it does not see one."""
    stride, size = sizes.kernel_stride, sizes.kernel_size
    keys = 0.5 * (means + jnp.concatenate(
        [means[..., 1:, :], jnp.zeros_like(means[..., :1, :])], axis=-2))
    scores = jnp.einsum("...gqd,...nd->...gqn", q.astype(jnp.float32), keys,
                        precision=jax.lax.Precision.HIGHEST) * scale
    seen = (jnp.arange(means.shape[-2]) * stride + size - 1
            <= t[..., None])                                   # [..., Q, N]
    scores = jnp.where(seen[..., None, :, :], scores, NEG_INF)
    top = jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.where(seen[..., None, :, :], jnp.exp(scores - top), 0.0)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    r = jnp.sum(weights / jnp.maximum(total, 1e-30), axis=-3)
    return jnp.where(seen, r, -1.0)


def chosen_mask(r, t, sizes: SparseSizes):
    """r [..., Q, N] (`compressed_scores`), t [..., Q] -> [..., Q, NB] bool,
    NB = N / per_block: the `topk` blocks each query attends to (all of them
    where there are fewer). A block's score is the largest r of the
    compressed keys that overlap it (those that begin in it and the one
    before); the first `init_blocks` and the `window_blocks` that end with
    the query's own count as chosen (+inf), blocks past the query's own are
    out (-2, under an unseen key's -1), ties go to the lower index. A block is
    in iff fewer than `topk` blocks beat it, block i beating j where its
    score is larger, or equal with i < j: `lax.top_k`'s set, found by
    counting and not by sorting (the comparisons [..., Q, NB, NB] are one
    fused reduction and never an array). A query with fewer than `topk` blocks
    behind it has blocks past its own among its chosen, the lowest first:
    the caller passes such a query by (`t + 1 < dense_len`)."""
    per = sizes.per_block
    blocks = r.shape[-1] // per
    grouped = r.reshape(r.shape[:-1] + (blocks, per))
    before = jnp.concatenate(
        [jnp.full_like(grouped[..., :1, -1], -1.0), grouped[..., :-1, -1]],
        axis=-1)
    score = jnp.maximum(jnp.max(grouped, axis=-1), before)     # [..., Q, NB]
    block = jnp.arange(blocks)
    own = (t // sizes.block_size)[..., None]
    forced = (block < sizes.init_blocks) | (
        (block <= own) & (block > own - sizes.window_blocks))
    score = jnp.where(forced, jnp.inf, jnp.where(block <= own, score, -2.0))
    rival, mine = score[..., None, :], score[..., :, None]  # [..., Q, j, i]
    beaten = (rival > mine) | (
        (rival == mine) & (block[None, :] < block[:, None]))
    return jnp.sum(beaten, axis=-1, dtype=jnp.int32) < min(sizes.topk, blocks)


def choose_blocks(r, t, sizes: SparseSizes):
    """r [..., Q, N] (`compressed_scores`), t [..., Q] -> the blocks
    `chosen_mask` says each query attends to as a list, [..., Q, topk] int32
    in ascending order (all N / per_block of them where there are fewer):
    entry k is the chosen block with k chosen blocks before it, both counted
    ([..., Q, NB, NB] and [..., Q, topk, NB] comparisons summed inside their
    fusions): no sort, no scan and no scatter."""
    mask = chosen_mask(r, t, sizes)
    blocks = mask.shape[-1]
    block = jnp.arange(blocks, dtype=jnp.int32)
    place = jnp.sum(mask[..., None, :] & (block[None, :] < block[:, None]),
                    axis=-1, dtype=jnp.int32)                  # [..., Q, NB]
    entry = jnp.arange(min(sizes.topk, blocks))[:, None]
    listed = mask[..., None, :] & (place[..., None, :] == entry)
    return jnp.sum(jnp.where(listed, block, 0), axis=-1)


QUERY_TILE = 512


def select_blocks(q, k, sizes: SparseSizes, scale: Optional[float] = None):
    """The key blocks every query of a sequence attends to, over the call's
    own q [B,S,H,D] and k [B,S,Hkv,D] at positions 0..S-1 -> [B,Hkv,NB,S]
    int32, 1 where the query at position t (last axis) attends to block j:
    the blocks up to its own while t + 1 < `dense_len`, its chosen `topk`
    from there on. Queries are scored `QUERY_TILE` at a time (a tile's
    scores are [H, QUERY_TILE, S / kernel_stride] float32), and only the
    tiles that reach `dense_len`; a tile's part of the result is
    `chosen_mask` itself, turned keys' blocks down: the `topk` are counted
    (ties to the lower index) and never listed, so nothing is sorted and
    nothing scattered. (A row's padding lies past every real query, and
    what a padded query chooses is never read.)"""
    sizes.check()
    b, s, h, d = q.shape
    hk = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bs, stride = sizes.block_size, sizes.kernel_stride
    nb = -(-s // bs)
    block = jnp.arange(nb)[:, None]
    t_all = jnp.arange(s)
    causal = jnp.broadcast_to((block <= t_all[None, :] // bs)[None, None],
                              (b, hk, nb, s)).astype(jnp.int32)
    if s <= sizes.dense_len - 1 or nb <= sizes.topk:
        return causal
    first = (sizes.dense_len - 1) // QUERY_TILE * QUERY_TILE
    kp = jnp.pad(k.astype(jnp.float32),
                 ((0, 0), (0, nb * bs - s), (0, 0), (0, 0)))
    means = kp.reshape(b, nb * sizes.per_block, stride, hk, d).mean(axis=2)
    means = means.transpose(0, 2, 1, 3)                        # [B,Hkv,N,D]
    tiles = -(-(s - first) // QUERY_TILE)
    qp = jnp.pad(q[:, first:], ((0, 0), (0, first + tiles * QUERY_TILE - s),
                                (0, 0), (0, 0)))
    qp = qp.reshape(b, tiles, QUERY_TILE, hk, h // hk, d).transpose(
        1, 0, 3, 4, 2, 5)                                   # [T,B,Hkv,G,Q,D]

    def tile(args):
        qt, start = args
        t = jnp.broadcast_to(start + jnp.arange(QUERY_TILE),
                             (b, hk, QUERY_TILE))
        picked = chosen_mask(
            compressed_scores(qt, means, t, sizes, scale), t, sizes)
        return picked.astype(jnp.int32).transpose(0, 1, 3, 2)  # [B,Hkv,NB,Q]

    picked = jax.lax.map(tile, (qp, first + jnp.arange(tiles) * QUERY_TILE))
    picked = picked.transpose(1, 2, 3, 0, 4).reshape(
        b, hk, nb, tiles * QUERY_TILE)[..., :s - first]
    sparse = jnp.concatenate([causal[..., :first], picked], axis=-1)
    return jnp.where(t_all + 1 < sizes.dense_len, causal, sparse)


def sparse_attention_plain(q, k, v, chosen, block_size: int,
                           scale: Optional[float] = None):
    """Causal attention in which the query at position t sees key j iff
    `chosen[b, g, j // block_size, t]` (`select_blocks`) and j <= t, as a
    masked dense softmax: q [B,S,H,D], k/v [B,S,Hkv,D]. What `sparse_flash`
    computes, in plain `jax.numpy` (the path without a cache, and the
    kernel's oracle)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k, v = _gqa_expand(k, v, h)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST) * scale
    ids = jnp.arange(s)
    of_key = jnp.repeat(chosen, block_size, axis=2)[:, :, :s]  # [B,Hkv,S,S]
    visible = (of_key.transpose(0, 1, 3, 2) > 0) & (ids[None, :]
                                                    <= ids[:, None])
    logits = jnp.where(jnp.repeat(visible, rep, axis=1), logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


def _sparse_flash_kernel(any_ref, q_ref, k_ref, v_ref, c_ref, o_ref, m_scr,
                         l_scr, acc_scr, *, scale: float, block_q: int,
                         block_k: int, block_size: int, rep: int,
                         num_k_blocks: int):
    """Grid (B, H, q tiles, k tiles), the scores of a tile held TRANSPOSED,
    keys down the sublanes and queries along the lanes: `c_ref` [block_k /
    block_size, block_q] says which of the tile's key blocks each query
    chose, and a block's flag is one row broadcast down its block_size
    sublanes; the running maximum and sum are rows [1, block_q], the
    accumulator [D, block_q]. `any_ref` (scalar prefetch) [B, Hkv, q tiles,
    k tiles], flat: whether any query of the tile chose (and may see) any key of
    it; a tile none did is passed over."""
    bi, hi = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    tile = ((bi * (pl.num_programs(1) // rep) + hi // rep)
            * pl.num_programs(2) + qi) * num_k_blocks + ki

    @pl.when(any_ref[tile] != 0)
    def _compute():
        q = q_ref[0, 0]                            # [block_q, d]
        k = k_ref[0, 0]                            # [block_k, d]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bk, bq]
        k_ids = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        q_ids = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        flags = c_ref[0, 0]                        # [bk / block_size, bq]
        chose = jnp.concatenate(
            [jnp.broadcast_to(flags[j:j + 1], (block_size, block_q))
             for j in range(block_k // block_size)], axis=0)
        s = jnp.where((chose > 0) & (k_ids <= q_ids), s, NEG_INF)
        m_prev = m_scr[...]                        # [1, bq]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=0, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0]                            # [bk, d]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [d, bq]

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


def sparse_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    chosen: jax.Array,
    *,
    block_size: int,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention under a block mask a query position, over the call's
    own q [B,S,H,D] and k/v [B,S,Hkv,D] (the Pallas kernel `sparse_flash`;
    forward only): the query at position t sees key j iff j <= t and
    `chosen[b, g, j // block_size, t]` (`select_blocks`: [B,Hkv,S/block_size,
    S] int32), g its KV head. The mathematics is `sparse_attention_plain`'s
    exactly: a tile reads a block some of its queries did not choose and
    masks it for them, and passes over a key tile none of its queries chose
    (or may see: above the diagonal). Every query must see a key of the
    first key tile (block 0 is always chosen). K and V keep their Hkv heads:
    a query head's index map reads its group's."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, s, h, d = q.shape
    hk = k.shape[2]
    rep = h // hk
    if s % block_size:
        raise ValueError(f"sequence {s} is not whole blocks of {block_size}")
    block_q, block_k = min(block_q, s), min(block_k, s)
    while s % block_q:
        block_q //= 2
    while s % block_k:
        block_k //= 2
    if block_k % block_size:
        raise ValueError(f"key tiles of {block_k} are not whole blocks of "
                         f"{block_size}")
    nq, nk, per = s // block_q, s // block_k, block_k // block_size
    # (a tile above the diagonal holds no pair a query may see)
    tiles = chosen.reshape(b, hk, nk, per, nq, block_q).max(axis=(3, 5))
    tiles = tiles.transpose(0, 1, 3, 2) * (
        jnp.arange(nk)[None, :] * block_k
        <= jnp.arange(nq)[:, None] * block_q + block_q - 1)
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    # (a key tile above the diagonal repeats the diagonal's index, so nothing
    # is fetched for it)
    below = lambda qi, ki: jnp.minimum(ki, (qi * block_q + block_q - 1)
                                       // block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki, any_: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda bi, hi, qi, ki, any_: (bi, hi // rep, below(qi, ki), 0))
    out = pl.pallas_call(
        functools.partial(_sparse_flash_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, block_size=block_size, rep=rep,
                          num_k_blocks=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                pl.BlockSpec((1, 1, per, block_q),
                             lambda bi, hi, qi, ki, any_: (
                                 bi, hi // rep, below(qi, ki), qi)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, d, block_q),
                lambda bi, hi, qi, ki, any_: (bi, hi, 0, qi)),
            scratch_shapes=[_vmem((1, block_q)), _vmem((1, block_q)),
                            _vmem((d, block_q))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d, s), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="sparse_flash",
    )(tiles.astype(jnp.int32).reshape(-1), qt, kt, vt, chosen)
    return out.transpose(0, 3, 1, 2)
