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
from typing import Optional, Tuple

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
