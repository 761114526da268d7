"""Paged K/V on the device: the pool's layout and everything that reads or
writes it (the engine's host half, allocator and prefix index, is
llm/_internal/paged.py; reference: ray.llm delegates paging to vLLM's CUDA
PagedAttention — here we ARE the engine, SURVEY §7.3).

TPU-first design: everything is static-shaped for XLA —
- pages:      [kv_heads, num_pages, page_size, head_dim] per layer (kv-head
  major so Pallas blocks tile the (page_size, head_dim) minor dims),
- page_table: [max_seqs, max_pages_per_seq] int32 (host-managed allocator),
- seq_lens:   [max_seqs] int32.
Writes are vectorized scatters (`.at[...].set(mode="drop")` — padding lanes
are sent out-of-bounds and dropped, so no dynamic shapes anywhere). The
decode gather reads each sequence's pages back as a contiguous view. A model
file calls `init_kv_pages` and `paged_write_attend` and knows no layout.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, NamedSharding, PartitionSpec

NEG_INF = -1e30


def pages_spec(shape: Tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """How pages [HK, P, ps, D] lie on a mesh: split over kv heads, or
    replicated where the tensor axis does not divide them (tiny test
    models). The cache and the decode kernel's shard_map both use it."""
    from ray_tpu.parallel.sharding import spec_for_shape

    return spec_for_shape(("kv_heads", None, None, None), shape, mesh)


def init_kv_pages(cache_cfg, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, mesh: Optional[Mesh] = None):
    """One layer's (k_pages, v_pages), layout [HK, P, ps, D], for a pool of
    `cache_cfg.num_pages` pages of `cache_cfg.page_size` tokens (the engine's
    `PagedCacheConfig`); with a mesh, created directly in their sharding (no
    device holds them all)."""
    shape = (kv_heads, cache_cfg.num_pages, cache_cfg.page_size, head_dim)
    sharding = (NamedSharding(mesh, pages_spec(shape, mesh))
                if mesh is not None else None)
    return (jnp.zeros(shape, dtype, device=sharding),
            jnp.zeros(shape, dtype, device=sharding))


def paged_write(pages: jax.Array, new_kv: jax.Array, page_table: jax.Array,
                positions: jax.Array, mask: jax.Array) -> jax.Array:
    """Scatter new_kv [B,S,HK,D] into pages [HK,P,ps,D].

    positions [B,S]: absolute token index of each entry; mask [B,S]: write
    enable (False lanes scatter out-of-bounds and are dropped)."""
    ps = pages.shape[2]
    page_idx = jnp.take_along_axis(
        page_table, positions // ps, axis=1)  # [B,S]
    slot_idx = positions % ps
    page_idx = jnp.where(mask, page_idx, pages.shape[1])  # OOB -> dropped
    hk, d = new_kv.shape[2], new_kv.shape[3]
    values = new_kv.reshape(-1, hk, d).swapaxes(0, 1)  # [HK,N,D]
    return pages.at[:, page_idx.reshape(-1), slot_idx.reshape(-1)].set(
        values, mode="drop")


def paged_gather(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """[HK,P,ps,D] + [B,MP] -> [B, MP*ps, HK, D] (each row's full context
    window, garbage beyond seq_len — callers mask)."""
    b, mp = page_table.shape
    hk, _, ps, d = pages.shape
    gathered = jnp.take(pages, page_table, axis=1)  # [HK,B,MP,ps,D]
    return gathered.reshape(hk, b, mp * ps, d).transpose(1, 2, 0, 3)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, q_positions: jax.Array,
                    seq_lens: jax.Array,
                    scale: Optional[float] = None,
                    use_kernel: Optional[bool] = None,
                    mesh: Optional[Mesh] = None) -> jax.Array:
    """Attention of q [B,S,H,D] over paged KV (causal by absolute position).

    q_positions [B,S]: absolute position of each query token; keys at
    absolute positions <= q_position and < seq_len are visible. The gather
    materializes [B, max_ctx] keys — fine for short prefill; single-token
    decode on a TPU takes the Pallas kernel below instead, which walks the
    pages in HBM (O(actual pages) traffic, not O(max)). `mesh` is the
    engine's tensor-parallel mesh: the kernel needs it (it cannot be
    partitioned by GSPMD), the gather path does not."""
    if use_kernel is None:
        use_kernel = q.shape[1] == 1 and jax.default_backend() == "tpu"
    if use_kernel and q.shape[1] == 1:
        return paged_attention_decode_kernel(
            q, k_pages, v_pages, page_table, seq_lens, scale=scale,
            mesh=mesh)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    h, hk = q.shape[2], k_pages.shape[0]
    k = paged_gather(k_pages, page_table)  # [B,C,HK,D]
    v = paged_gather(v_pages, page_table)
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    ctx = k.shape[1]
    k_pos = jnp.arange(ctx)[None, None, :]  # absolute position within slot
    visible = (k_pos <= q_positions[:, :, None]) & (
        k_pos < seq_lens[:, None, None])
    logits = jnp.where(visible[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def paged_write_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                       kv_pages: Tuple[jax.Array, jax.Array],
                       page_table: jax.Array, positions: jax.Array,
                       write_mask: jax.Array, seq_lens: jax.Array,
                       mesh: Optional[Mesh] = None):
    """One attention layer's step over its pool: this call's k and v
    [B,S,HK,D] written at `positions` [B,S] where `write_mask` allows, then
    q [B,S,H,D] attended over the pool. Returns (out [B,S,H,D], (k_pages,
    v_pages)): all a model file needs of the pool."""
    k_pages, v_pages = kv_pages
    k_pages = paged_write(k_pages, k, page_table, positions, write_mask)
    v_pages = paged_write(v_pages, v, page_table, positions, write_mask)
    out = paged_attention(q, k_pages, v_pages, page_table, positions,
                          seq_lens, mesh=mesh)
    return out, (k_pages, v_pages)


# ---------------------------------------------------------------------------
# Pallas TPU paged-attention decode kernel
# ---------------------------------------------------------------------------
def _paged_decode_kernel(pt_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, ksem, vsem, m_scr, l_scr, acc_scr, *,
                         page_size: int, pages_per_chunk: int,
                         max_pages: int, scale: float):
    """Grid (B, HK). KV pages stay in HBM; the kernel walks the sequence's
    page list in chunks of C pages, double-buffering the page DMAs against
    the flash update of the previous chunk (the canonical TPU
    paged-attention shape — per-page grid steps would be DMA-latency
    bound)."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    hki = pl.program_id(1)
    C = pages_per_chunk
    ps = page_size
    seq_len = lens_ref[b]
    n_pages = jax.lax.div(seq_len + ps - 1, ps)
    n_chunks = jax.lax.div(n_pages + C - 1, C)

    def start_chunk(ci, buf):
        for j in range(C):  # static unroll: C independent page DMAs
            pg = ci * C + j

            @pl.when(pg < n_pages)
            def _():
                page = pt_ref[b, pg]
                pltpu.make_async_copy(
                    k_hbm.at[hki, page], kbuf.at[buf, j], ksem.at[buf, j],
                ).start()
                pltpu.make_async_copy(
                    v_hbm.at[hki, page], vbuf.at[buf, j], vsem.at[buf, j],
                ).start()

            @pl.when(pg >= n_pages)
            def _zero():
                # Unfetched slots must hold zeros, not garbage: their
                # probability weights are exactly 0, but 0 * NaN = NaN in
                # the p·v accumulation.
                vbuf[buf, j] = jnp.zeros_like(vbuf[buf, j])
                kbuf[buf, j] = jnp.zeros_like(kbuf[buf, j])

    def wait_chunk(ci, buf):
        for j in range(C):
            pg = ci * C + j

            @pl.when(pg < n_pages)
            def _():
                page = pt_ref[b, pg]
                pltpu.make_async_copy(
                    k_hbm.at[hki, page], kbuf.at[buf, j], ksem.at[buf, j],
                ).wait()
                pltpu.make_async_copy(
                    v_hbm.at[hki, page], vbuf.at[buf, j], vsem.at[buf, j],
                ).wait()

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    start_chunk(0, 0)

    # Static unroll over the page-table capacity: every buffer index is a
    # compile-time constant; per-sequence work is guarded by n_chunks.
    chunks_max = (max_pages + C - 1) // C
    for ci in range(chunks_max):
        buf = ci % 2

        @pl.when(ci < n_chunks)
        def _chunk(ci=ci, buf=buf):
            if ci + 1 < chunks_max:
                @pl.when(ci + 1 < n_chunks)
                def _prefetch():
                    start_chunk(ci + 1, 1 - buf)

            wait_chunk(ci, buf)
            q = q_ref[0, 0]  # [Hg, D]
            k = kbuf[buf].reshape(C * ps, -1)  # [C*ps, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Hg, C*ps]
            pos = ci * C * ps + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < seq_len, s, NEG_INF)
            m_prev = m_scr[:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=-1)
            m_scr[:, 0] = m_new
            v = vbuf[buf].reshape(C * ps, -1)
            acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    denom = jnp.maximum(l_scr[:, 0], 1e-30)
    o_ref[0, 0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


def paged_attention_decode_kernel(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        page_table: jax.Array, seq_lens: jax.Array,
        scale: Optional[float] = None,
        pages_per_chunk: int = 16,
        interpret: Optional[bool] = None,
        mesh: Optional[Mesh] = None) -> jax.Array:
    """Pallas decode attention: q [B,1,H,D] over paged KV without
    materializing the gathered context. Grid (B, KV_H); q heads are grouped
    by kv head (GQA) so one [Hg, C*ps] MXU tile serves all query heads of
    the group per chunk; see _paged_decode_kernel for the DMA pipeline.

    With a multi-device `mesh` the kernel runs under shard_map with the KV
    heads (and the query heads grouped under them) split over the tensor
    axis, as the engine shards the pages; page table and lengths ride along
    replicated."""
    from jax.experimental.pallas import tpu as pltpu

    if mesh is not None and mesh.size > 1:
        kv_spec = pages_spec(k_pages.shape, mesh)
        q_spec = PartitionSpec(None, None, *kv_spec[:1])
        local = functools.partial(
            paged_attention_decode_kernel, scale=scale,
            pages_per_chunk=pages_per_chunk, interpret=interpret)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec, PartitionSpec(),
                      PartitionSpec()),
            out_specs=q_spec, check_vma=False,
        )(q, k_pages, v_pages, page_table, seq_lens)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, s, h, d = q.shape
    assert s == 1, "decode kernel expects one query token per sequence"
    hk, num_pages, ps, _ = k_pages.shape
    hg = h // hk
    mp = page_table.shape[1]
    C = min(pages_per_chunk, mp)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, hk, hg, d)

    kernel = functools.partial(
        _paged_decode_kernel, page_size=ps, pages_per_chunk=C,
        max_pages=mp, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk),
            in_specs=[
                pl.BlockSpec((1, 1, hg, d),
                             lambda bi, hki, pt, lens: (bi, hki, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, hg, d), lambda bi, hki, pt, lens: (bi, hki, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, C, ps, d), k_pages.dtype),
                pltpu.VMEM((2, C, ps, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.VMEM((hg, 1), jnp.float32),
                pltpu.VMEM((hg, 1), jnp.float32),
                pltpu.VMEM((hg, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, hg, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(page_table, seq_lens, qg, k_pages, v_pages)
    return out.reshape(b, 1, h, d)
