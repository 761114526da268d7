"""Paged K/V on the device: the pool's layout and everything that reads or
writes it (the engine's host half, allocator and prefix index, is
llm/_internal/paged.py; reference: ray.llm delegates paging to vLLM's CUDA
PagedAttention — here we ARE the engine, SURVEY §7.3).

TPU-first design: everything is static-shaped for XLA —
- pages:      [num_pages, page_size, kv_heads * head_dim] per layer, token
  major: a token's K (or V) over all its heads is one contiguous row, a page
  one contiguous [page_size, kv_heads * head_dim] tile, and kv head g is the
  lane block [g * head_dim, (g + 1) * head_dim) of a row. The scatter that
  writes a token, the gather that reads a prefix back and the decode kernel
  all take this one layout as it lies, so no program copies a pool to call
  any of them,
- page_table: [max_seqs, max_pages_per_seq] int32 (host-managed allocator),
- seq_lens:   [max_seqs] int32.
Writes are vectorized scatters (`.at[...].set(mode="drop")` — padding lanes
are sent out-of-bounds and dropped, so no dynamic shapes anywhere). The
decode gather reads each sequence's pages back as a contiguous view. A model
file calls `init_kv_pages` and `paged_write_attend` and knows no layout.

A sliding-window layer keeps no pages from the allocator but a RING a slot
(`init_ring_pages`, `ring_write`, `ring_attention`): `ring_pages(window, ps)`
= window / ps + 1 pages of the same layout, slot s owning pages s R .. s R +
R - 1, position p living in ring page (p // ps) % R. The keys a query may
still see (the last `window` positions) span at most R pages, so a write
only ever lands on a page whose keys are all dead, and the layer's bytes do
not grow with the context.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.ops import attention

NEG_INF = -1e30
# VMEM the decode kernel's double-buffered K and V page chunks may take
# (of the 16 MiB a v5e kernel gets by default; the rest is the compiler's).
KV_CHUNK_VMEM_BYTES = 2 * 2 ** 20


def pages_spec(kv_heads: int, mesh: Mesh) -> PartitionSpec:
    """How pages [P, ps, HK*D] lie on a mesh: the lanes split over the
    tensor axis where it divides the KV heads (whole heads a shard), or
    replicated where it does not (tiny test models). The cache and the
    decode kernel's shard_map both use it."""
    from ray_tpu.parallel.sharding import spec_for_shape

    heads = spec_for_shape(("kv_heads",), (kv_heads,), mesh)
    return PartitionSpec(None, None, heads[0] if len(heads) else None)


def init_kv_pages(cache_cfg, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, mesh: Optional[Mesh] = None):
    """One layer's (k_pages, v_pages), layout [P, ps, HK*D], for a pool of
    `cache_cfg.num_pages` pages of `cache_cfg.page_size` tokens (the engine's
    `PagedCacheConfig`); with a mesh, created directly in their sharding (no
    device holds them all)."""
    shape = (cache_cfg.num_pages, cache_cfg.page_size, kv_heads * head_dim)
    sharding = (NamedSharding(mesh, pages_spec(kv_heads, mesh))
                if mesh is not None else None)
    return (jnp.zeros(shape, dtype, device=sharding),
            jnp.zeros(shape, dtype, device=sharding))


LANES = 128  # a tile of the device's memory is this many lanes wide


def init_latent_pages(cache_cfg, width: int, dtype=jnp.bfloat16):
    """One latent-attention layer's pool from the allocator's pages: a token's
    `width` values on whole tiles of lanes, [P, ps, 640] for 576, the lanes
    past `width` zero. The device pads a minor axis to whole tiles whatever
    its shape says (so the bytes are these either way), and the chip's
    compiler takes no DMA of a page whose lanes are not whole tiles; stated,
    the padding is the kernel's to read as it lies. One array and not two of
    512 and 64 lanes: the second would be padded to 128, the same 640, and
    cost the decode kernel a second DMA a page."""
    lanes = -(-width // LANES) * LANES
    return jnp.zeros((cache_cfg.num_pages, cache_cfg.page_size, lanes), dtype)


def latent_write(pages: jax.Array, rows: jax.Array, page_table: jax.Array,
                 positions: jax.Array, mask: jax.Array) -> jax.Array:
    """`paged_write` of rows [B,S,width] into a latent pool, each padded with
    zeros to the pool's lanes."""
    pad = pages.shape[-1] - rows.shape[-1]
    return paged_write(pages, jnp.pad(rows, ((0, 0), (0, 0), (0, pad))),
                       page_table, positions, mask)


def paged_write(pages: jax.Array, new_kv: jax.Array, page_table: jax.Array,
                positions: jax.Array, mask: jax.Array,
                wrap: bool = False) -> jax.Array:
    """Scatter new_kv [B,S,HK,D] into pages [P,ps,HK*D], a row a token.

    positions [B,S]: absolute token index of each entry; mask [B,S]: write
    enable (False lanes scatter out-of-bounds and are dropped). `wrap`: the
    table's columns are a ring, page n of a row is column n % columns."""
    num_pages, ps, width = pages.shape
    cols = positions // ps
    if wrap:
        cols = cols % page_table.shape[1]
    page_idx = jnp.take_along_axis(page_table, cols, axis=1)  # [B,S]
    slot_idx = positions % ps
    page_idx = jnp.where(mask, page_idx, num_pages)  # OOB -> dropped
    return pages.at[page_idx.reshape(-1), slot_idx.reshape(-1)].set(
        new_kv.reshape(-1, width), mode="drop")


def paged_gather(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """[P,ps,HK*D] + [B,MP] -> [B, MP*ps, HK*D] (each row's full context
    window in token order, garbage beyond seq_len — callers mask)."""
    b, mp = page_table.shape
    _, ps, width = pages.shape
    return jnp.take(pages, page_table, axis=0).reshape(b, mp * ps, width)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, q_positions: jax.Array,
                    seq_lens: jax.Array,
                    scale: Optional[float] = None,
                    use_kernel: Optional[bool] = None,
                    mesh: Optional[Mesh] = None,
                    block_length: int = 1) -> jax.Array:
    """Attention of q [B,S,H,D] over paged KV (causal by absolute position).

    q_positions [B,S]: absolute position of each query token; keys at
    absolute positions <= q_position and < seq_len are visible. The gather
    materializes [B, max_ctx] keys — fine for short prefill; single-token
    decode on a TPU takes the Pallas kernel below instead, which walks the
    pages in HBM (O(actual pages) traffic, not O(max)). `mesh` is the
    engine's tensor-parallel mesh: the kernel needs it (it cannot be
    partitioned by GSPMD), the gather path does not.

    `block_length` > 1 (a power of two) is block diffusion's visibility:
    positions come in aligned blocks of that many, and a query sees the keys
    up to the end of its own block (and < seq_len). The decode step is then
    one whole block a row, S = block_length queries that all see the same
    keys, which the kernel takes as so many more heads."""
    if use_kernel is None:
        use_kernel = (q.shape[1] == block_length
                      and jax.default_backend() == "tpu")
    if use_kernel and q.shape[1] == block_length:
        return paged_attention_decode_kernel(
            q, k_pages, v_pages, page_table, seq_lens, scale=scale,
            mesh=mesh)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, _, h, d = q.shape
    hk = k_pages.shape[2] // d
    k = paged_gather(k_pages, page_table).reshape(b, -1, hk, d)  # [B,C,HK,D]
    v = paged_gather(v_pages, page_table).reshape(b, -1, hk, d)
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    ctx = k.shape[1]
    k_pos = jnp.arange(ctx)[None, None, :]  # absolute position within slot
    if block_length > 1:  # to the end of the query's own block
        q_positions = q_positions | (block_length - 1)
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
                       mesh: Optional[Mesh] = None, block_length: int = 1):
    """One attention layer's step over its pool: this call's k and v
    [B,S,HK,D] written at `positions` [B,S] where `write_mask` allows, then
    q [B,S,H,D] attended over the pool (`block_length`: see
    `paged_attention`). Returns (out [B,S,H,D], (k_pages, v_pages)): all a
    model file needs of the pool."""
    k_pages, v_pages = kv_pages
    k_pages = paged_write(k_pages, k, page_table, positions, write_mask)
    v_pages = paged_write(v_pages, v, page_table, positions, write_mask)
    out = paged_attention(q, k_pages, v_pages, page_table, positions,
                          seq_lens, mesh=mesh, block_length=block_length)
    return out, (k_pages, v_pages)


# ---------------------------------------------------------------------------
# A ring of pages a slot: the cache of a sliding-window layer
# ---------------------------------------------------------------------------
def ring_pages(window: int, page_size: int) -> int:
    """Pages of a slot's ring: the last `window` positions of any length
    span at most this many pages of `page_size` (which divides `window`)."""
    return window // page_size + 1


def init_ring_pages(cache_cfg, window: int, kv_heads: int, head_dim: int,
                    dtype=jnp.bfloat16):
    """One sliding-window layer's (k_pages, v_pages): `max_seqs` rings of
    `ring_pages` pages, layout as `init_kv_pages`. Nothing of it depends on
    `max_pages_per_seq` or the allocator's pool."""
    if window % cache_cfg.page_size:
        raise ValueError(f"sliding_window {window} is not a multiple of "
                         f"page_size {cache_cfg.page_size}")
    shape = (cache_cfg.max_seqs * ring_pages(window, cache_cfg.page_size),
             cache_cfg.page_size, kv_heads * head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def ring_table(slots: jax.Array, ring: int) -> jax.Array:
    """[B] slots -> [B, ring]: the pages of each slot's ring, in ring
    order."""
    return slots[:, None] * ring + jnp.arange(ring, dtype=slots.dtype)


def ring_write(pages: jax.Array, new_kv: jax.Array, slots: jax.Array,
               positions: jax.Array, mask: jax.Array, seq_lens: jax.Array,
               window: int) -> jax.Array:
    """`paged_write` into the rings of `slots` [B]: new_kv [B,S,HK,D] at
    `positions` [B,S] of rows that end at `seq_lens` [B] with this call. Only
    the positions of a row's last `ring_pages` pages are written: an earlier
    one shares its ring page with a later one of the same call (a scatter
    with two writers to one row is not ordered), and no later query sees
    it."""
    ps = pages.shape[1]
    ring = ring_pages(window, ps)
    alive = positions // ps + ring > (seq_lens[:, None] - 1) // ps
    return paged_write(pages, new_kv, ring_table(slots, ring), positions,
                       mask & alive, wrap=True)


def ring_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                   slots: jax.Array, seq_lens: jax.Array, window: int,
                   scale: Optional[float] = None,
                   use_kernel: Optional[bool] = None) -> jax.Array:
    """One decode step of a sliding-window layer: q [B,1,H,D], the query at
    position `seq_lens` - 1 (already written), over the keys at positions
    `seq_lens` - `window` .. `seq_lens` - 1 of the rings of `slots` [B]. On a
    TPU the decode kernel walks the window's pages (`swa_decode`); elsewhere
    the rings are gathered whole and every cell's position worked out from
    the row's length."""
    ps = k_pages.shape[1]
    ring = ring_pages(window, ps)
    table = ring_table(slots, ring)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return paged_attention_decode_kernel(
            q, k_pages, v_pages, table, seq_lens, scale=scale, window=window)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, _, h, d = q.shape
    hk = k_pages.shape[2] // d
    k, v = (jnp.repeat(paged_gather(pages, table).reshape(b, -1, hk, d),
                       h // hk, axis=2) for pages in (k_pages, v_pages))
    # Column c of a ring holds the newest page n <= the row's last page with
    # n % ring == c (a page before the row's start: nothing).
    last_page = ((seq_lens - 1) // ps)[:, None]
    page = last_page - (last_page - jnp.arange(ring)) % ring     # [B, ring]
    pos = (page[:, :, None] * ps + jnp.arange(ps)).reshape(b, -1)
    lens = seq_lens[:, None]
    visible = (pos >= 0) & (pos < lens) & (pos >= lens - window)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(visible[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU paged-attention decode kernel
# ---------------------------------------------------------------------------
def _paged_decode_kernel(pt_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, ksem, vsem, m_scr, l_scr, acc_scr, *,
                         kv_heads: int, scale: float,
                         window: Optional[int] = None):
    """Grid (B,): one program a sequence, over all its heads. KV pages stay
    in HBM; the kernel walks the sequence's page list in chunks of C pages,
    a page one DMA of [ps, HK*D], double-buffering the page DMAs against the
    flash update of the previous chunk (the canonical TPU paged-attention
    shape — per-page grid steps would be DMA-latency bound).

    Heads are told apart by lanes, not by a loop: row h of `q_wide` holds
    query head h in its kv head's lane block and zeros elsewhere, so one
    contraction over all HK*D lanes of the keys gives every head's scores,
    and of p @ v [H, HK*D] each head keeps its own block at the end. Groups
    of several query heads (GQA) and of one run the same code."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    _, C, ps, width = kbuf.shape
    h, d = q_ref.shape[2:]
    # Never past the page table, whatever length a caller hands a row it
    # does not use (the engine's decode programs start a free slot's row at
    # 0 every window, so it walks one page).
    if window is None:
        seq_len = jnp.minimum(lens_ref[b], pt_ref.shape[1] * ps)
        n_pages = jax.lax.div(seq_len + ps - 1, ps)
    else:
        # (a ring bounds no length; the walk is R pages at most)
        seq_len = lens_ref[b]
        first = jax.lax.div(jnp.maximum(seq_len - window, 0), ps)
        n_pages = jax.lax.div(seq_len + ps - 1, ps) - first
    n_chunks = jax.lax.div(n_pages + C - 1, C)

    def page_copies(ci, buf, j):
        if window is None:
            page = pt_ref[b, ci * C + j]
        else:
            page = pt_ref[b, jax.lax.rem(first + ci * C + j,
                                         pt_ref.shape[1])]
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, j],
                                      ksem.at[buf, j]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, j],
                                      vsem.at[buf, j]))

    def start_chunk(ci, buf):
        for j in range(C):  # static unroll: C independent page DMAs

            @pl.when(ci * C + j < n_pages)
            def _(j=j):
                for copy in page_copies(ci, buf, j):
                    copy.start()

            @pl.when(ci * C + j >= n_pages)
            def _zero(j=j):
                # Unfetched slots must hold zeros, not garbage: their
                # probability weights are exactly 0, but 0 * NaN = NaN in
                # the p·v accumulation.
                vbuf[buf, j] = jnp.zeros_like(vbuf[buf, j])
                kbuf[buf, j] = jnp.zeros_like(kbuf[buf, j])

    def wait_chunk(ci, buf):
        for j in range(C):

            @pl.when(ci * C + j < n_pages)
            def _(j=j):
                for copy in page_copies(ci, buf, j):
                    copy.wait()

    # own[h, lane]: the lane lies in the block of query head h's kv head
    own = (jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // d
           == jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0)
           // (h // kv_heads))
    q_wide = jnp.where(own, jnp.tile(q_ref[0, 0], (1, kv_heads)), 0)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _first():
        start_chunk(0, 0)

    def chunk(ci, carry):
        buf = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _prefetch():
            start_chunk(ci + 1, 1 - buf)

        wait_chunk(ci, buf)
        k = kbuf[buf].reshape(C * ps, width)
        s = jax.lax.dot_general(
            q_wide, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, C*ps]
        pos = ci * C * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if window is None:
            s = jnp.where(pos < seq_len, s, NEG_INF)
        else:
            pos = pos + first * ps
            s = jnp.where((pos < seq_len) & (pos >= seq_len - window), s,
                          NEG_INF)
        m_prev = m_scr[...]  # [H, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = vbuf[buf].reshape(C * ps, width)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, HK*D]
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)

    acc = jnp.where(own, acc_scr[...], 0.0)
    out = sum(acc[:, g * d:(g + 1) * d] for g in range(kv_heads))  # [H, D]
    o_ref[0, 0] = (out / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_decode_kernel(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        page_table: jax.Array, seq_lens: jax.Array,
        scale: Optional[float] = None,
        pages_per_chunk: Optional[int] = None,
        interpret: Optional[bool] = None,
        mesh: Optional[Mesh] = None,
        window: Optional[int] = None) -> jax.Array:
    """Pallas decode attention: q [B,S,H,D] over paged KV [P,ps,HK*D] without
    materializing the gathered context. Grid (B,); see _paged_decode_kernel
    for the DMA pipeline. `pages_per_chunk` defaults to what
    KV_CHUNK_VMEM_BYTES holds of this pool's pages, twice for K and for V.

    All S queries of a row see the same keys, those below `seq_lens` (S = 1:
    the new token; S > 1: one block of block diffusion, already written), so
    they are folded under their KV heads as S times the query heads, order
    (kv head, position, head of the group), and the kernel is the same.

    With a multi-device `mesh` the kernel runs under shard_map with the KV
    heads (and the query heads grouped under them) split over the tensor
    axis, as the engine shards the pages; page table and lengths ride along
    replicated.

    `window`: a sliding-window layer's step over its rings (`page_table` a
    `ring_table`; S = 1, no mesh). The call is then named `swa_decode`, so a
    trace tells the two apart."""
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    _, ps, width = k_pages.shape
    hk = width // d
    if window is not None and (s > 1 or mesh is not None):
        raise NotImplementedError("a windowed decode step is one query a "
                                  "row on one device")
    if s > 1:
        fold = lambda t, a, c: t.reshape(b, a, hk, c, h // hk, d).transpose(
            0, 3, 2, 1, 4, 5)
        out = paged_attention_decode_kernel(
            fold(q, s, 1).reshape(b, 1, s * h, d), k_pages, v_pages,
            page_table, seq_lens, scale=scale,
            pages_per_chunk=pages_per_chunk, interpret=interpret, mesh=mesh)
        return fold(out, 1, s).reshape(b, s, h, d)
    if mesh is not None and mesh.size > 1:
        kv_spec = pages_spec(hk, mesh)
        q_spec = PartitionSpec(None, None, kv_spec[2])
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
    mp = page_table.shape[1]
    if pages_per_chunk is None:
        page_bytes = ps * width * k_pages.dtype.itemsize
        pages_per_chunk = max(1, KV_CHUNK_VMEM_BYTES // (4 * page_bytes))
    C = min(pages_per_chunk, mp)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_decode_kernel, kv_heads=hk, scale=scale,
                               window=window)
    block = pl.BlockSpec((1, 1, h, d), lambda bi, pt, lens: (bi, 0, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, C, ps, width), k_pages.dtype),
                pltpu.VMEM((2, C, ps, width), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_decode" if window is None else "swa_decode",
    )(page_table, seq_lens, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Latent attention (MLA): one pool, read once, keys and values alike
# ---------------------------------------------------------------------------
def latent_attention(q: jax.Array, pages: jax.Array, page_table: jax.Array,
                     seq_lens: jax.Array, rank: int, scale: float,
                     use_kernel: Optional[bool] = None) -> jax.Array:
    """One decode step of a latent-attention layer in its absorbed form:
    q [B,H,width], head h's query already carried into the latent's space
    (`rank` lanes) beside its rotary part, over the rows at positions below
    `seq_lens` of a latent pool [P,ps,lanes] (the step's own row written).
    softmax(scale q . row) over all the row's lanes, times the rows' first
    `rank` lanes -> [B,H,rank]; the caller carries that out of the latent's
    space. On a TPU the kernel `mla_decode` walks the pages; elsewhere they
    are gathered whole."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    # (the pool's padding lanes are zero: so are the query's)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pages.shape[-1] - q.shape[-1])))
    if use_kernel:
        return mla_decode(q, pages, page_table, seq_lens, rank, scale)
    rows = paged_gather(pages, page_table).astype(jnp.float32)  # [B,C,width]
    logits = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows) * scale
    visible = jnp.arange(rows.shape[1])[None, :] < seq_lens[:, None]
    logits = jnp.where(visible[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", probs,
                      rows[..., :rank]).astype(q.dtype)


def _mla_decode_kernel(pt_ref, lens_ref, q_ref, pages_hbm, o_ref, buf, sem,
                       m_scr, l_scr, acc_scr, *, rank: int, scale: float):
    """Grid (B,): one program a sequence, over all its heads, the page walk
    of `_paged_decode_kernel` (chunks of C pages, a page one DMA of
    [ps, width], the next chunk's DMAs in flight under this chunk's flash
    update) over ONE pool: the query tile [H, width] against a chunk's rows
    gives every head's scores in one contraction, and the weights against
    the same rows' first `rank` lanes the running sum [H, rank]."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    _, C, ps, width = buf.shape
    # (never past the page table: `_paged_decode_kernel` says why)
    seq_len = jnp.minimum(lens_ref[b], pt_ref.shape[1] * ps)
    n_pages = jax.lax.div(seq_len + ps - 1, ps)
    n_chunks = jax.lax.div(n_pages + C - 1, C)

    def page_copy(ci, slot, j):
        return pltpu.make_async_copy(pages_hbm.at[pt_ref[b, ci * C + j]],
                                     buf.at[slot, j], sem.at[slot, j])

    def start_chunk(ci, slot):
        for j in range(C):  # static unroll: C independent page DMAs

            @pl.when(ci * C + j < n_pages)
            def _(j=j):
                page_copy(ci, slot, j).start()

            @pl.when(ci * C + j >= n_pages)
            def _zero(j=j):
                # (a weight of exactly 0 times garbage may be NaN)
                buf[slot, j] = jnp.zeros_like(buf[slot, j])

    def wait_chunk(ci, slot):
        for j in range(C):

            @pl.when(ci * C + j < n_pages)
            def _(j=j):
                page_copy(ci, slot, j).wait()

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _first():
        start_chunk(0, 0)

    def chunk(ci, carry):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _prefetch():
            start_chunk(ci + 1, 1 - slot)

        wait_chunk(ci, slot)
        rows = buf[slot].reshape(C * ps, width)
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, C*ps]
        pos = ci * C * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_scr[...]  # [H, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, rank]
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
        o_ref.dtype)


def mla_decode(q: jax.Array, pages: jax.Array, page_table: jax.Array,
               seq_lens: jax.Array, rank: int, scale: float,
               pages_per_chunk: Optional[int] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """Pallas decode attention over a latent pool: q [B,H,lanes] over pages
    [P,ps,lanes] -> [B,H,rank] (`latent_attention` says what of what). Grid
    (B,); a page is read once, as keys and as values. `pages_per_chunk`
    defaults to what KV_CHUNK_VMEM_BYTES holds of this pool's pages, twice
    (the chunk in use and the one in flight)."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, width = q.shape
    _, ps, _ = pages.shape
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if pages_per_chunk is None:
        page_bytes = ps * width * pages.dtype.itemsize
        pages_per_chunk = max(1, KV_CHUNK_VMEM_BYTES // (2 * page_bytes))
    C = min(pages_per_chunk, page_table.shape[1])
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, width), lambda bi, pt, lens: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, rank),
                                   lambda bi, pt, lens: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, C, ps, width), pages.dtype),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mla_decode",
    )(page_table, seq_lens, q, pages)


# ---------------------------------------------------------------------------
# Learned block-sparse attention: an index of segment means beside K/V, a
# list of chosen pages a row and KV head, and the walk over that list
# ---------------------------------------------------------------------------
def init_index_pages(cache_cfg, segments: int, width: int):
    """One sparse-attention layer's index pool beside its (k_pages, v_pages):
    a page's `segments` segment means (the mean of each run of page_size /
    segments keys, all KV heads side by side as a key's row is), float32
    [P, segments, HK*D] from the allocator's page count."""
    return jnp.zeros((cache_cfg.num_pages, segments, width), jnp.float32)


def index_write(m_pages: jax.Array, k: jax.Array, page_table: jax.Array,
                positions: jax.Array, mask: jax.Array,
                page_size: int) -> jax.Array:
    """A prefill's whole segments into the index pool: k [B,S,HK,D] at
    `positions` [B,S] (a row's first at a segment's start, S whole segments)
    of pages of `page_size` keys; a segment is written where `mask` [B,S]
    holds its last key."""
    num_pages, segments, width = m_pages.shape
    b, s = positions.shape
    seg = page_size // segments
    means = k.astype(jnp.float32).reshape(b, s // seg, seg, width).mean(axis=2)
    at = positions[:, ::seg]
    page = jnp.take_along_axis(page_table, at // page_size, axis=1)
    page = jnp.where(mask[:, seg - 1::seg], page, num_pages)  # OOB -> dropped
    return m_pages.at[page.reshape(-1),
                      (at % page_size // seg).reshape(-1)].set(
        means.reshape(-1, width), mode="drop")


def index_step(m_pages: jax.Array, k_pages: jax.Array, page_table: jax.Array,
               positions: jax.Array, mask: jax.Array) -> jax.Array:
    """A decode step's part of the index: where the key just written at
    `positions` [B] (where `mask` [B]) is the last of its segment, that
    segment's mean, taken from the page's own rows, is written. In place on
    a donated pool: a gather of B segments and a scatter of B rows."""
    num_pages, segments, _ = m_pages.shape
    ps = k_pages.shape[1]
    seg = ps // segments
    page = jnp.take_along_axis(page_table, (positions // ps)[:, None],
                               axis=1)[:, 0]
    which = positions % ps // seg
    rows = (which * seg)[:, None] + jnp.arange(seg)
    means = k_pages[page[:, None], rows].astype(jnp.float32).mean(axis=1)
    done = mask & (positions % seg == seg - 1)
    return m_pages.at[jnp.where(done, page, num_pages), which].set(
        means, mode="drop")


def select_pages(q: jax.Array, m_pages: jax.Array, page_table: jax.Array,
                 seq_lens: jax.Array, sizes, scale: Optional[float] = None,
                 active: Optional[jax.Array] = None):
    """The pages a decode step's queries walk. q [B,H,D] at positions
    `seq_lens` - 1 (already written), `m_pages` the layer's index pool,
    `sizes` an `ops.attention.SparseSizes` whose block is a page. A row
    shorter than `dense_len` lists its own pages in order; a longer one, for
    each KV head, the `topk` pages its group chooses (`compressed_scores`,
    `choose_blocks` over the row's own pages' segment means: a page is in
    where fewer than `topk` beat it, ties to the lower index, counted and
    not sorted), in ascending order. -> (pages [B,HK,L] physical, counts
    [B,HK,L] valid tokens of each, used [B,HK] entries in use, load [2]
    int32 = (pages_selected, pages_visible): sums over the `active` rows and
    KV heads of `used` and of ceil(len / page)), L = max(topk, dense_len /
    page): one shape, so one decode program for both kinds of row."""
    b, h, d = q.shape
    _, per, width = m_pages.shape
    hk = width // d
    bs = sizes.block_size
    mp = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    means = jnp.take(m_pages, page_table, axis=0).reshape(b, mp * per, hk, d)
    t = jnp.broadcast_to((seq_lens - 1)[:, None, None], (b, hk, 1))
    r = attention.compressed_scores(q.reshape(b, hk, h // hk, 1, d),
                                    means.transpose(0, 2, 1, 3), t, sizes,
                                    scale)
    chosen = attention.choose_blocks(r, t, sizes)[:, :, 0]     # [B,HK,topk]
    width_l = max(sizes.topk, sizes.dense_len // bs)
    own = jnp.arange(width_l, dtype=jnp.int32)
    sparse = (seq_lens >= sizes.dense_len)[:, None]            # [B,1]
    held = -(-seq_lens // bs)                                  # pages a row
    idx = jnp.where(sparse[..., None], jnp.pad(
        chosen, ((0, 0), (0, 0), (0, width_l - chosen.shape[-1]))), own)
    pages = jnp.take_along_axis(page_table[:, None, :],
                                jnp.minimum(idx, mp - 1), axis=2)
    counts = jnp.clip(seq_lens[:, None, None] - idx * bs, 0, bs)
    used = jnp.broadcast_to(jnp.where(sparse, sizes.topk, held[:, None]),
                            (b, hk))
    if active is None:
        active = jnp.ones((b,), bool)
    load = jnp.stack([jnp.sum(jnp.where(active[:, None], used, 0)),
                      jnp.sum(jnp.where(active, held, 0)) * hk])
    return (pages.astype(jnp.int32), counts.astype(jnp.int32),
            used.astype(jnp.int32), load.astype(jnp.int32))


def _sparse_decode_kernel(pages_ref, counts_ref, used_ref, q_ref, k_hbm,
                          v_hbm, o_ref, kbuf, vbuf, ksem, vsem, m_scr, l_scr,
                          acc_scr, *, scale: float):
    """Grid (B, HK): one program a row and KV head, its group's query heads
    [G, D] against the pages of `pages_ref[row]` in the order listed, entry j
    valid for its first `counts_ref[row, j]` tokens, `used_ref[row]` entries
    in all. `_paged_decode_kernel`'s pipeline (chunks of C pages, a page one
    DMA, the next chunk's DMAs in flight under this chunk's flash update)
    over a list and not a table's row, and of a page only this KV head's
    lanes [ps, D]: the other head's keys are not read."""
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(1)
    row = pl.program_id(0) * pl.num_programs(1) + g
    _, C, ps, d = kbuf.shape
    width = pages_ref.shape[1]
    n_pages = used_ref[row]
    n_chunks = jax.lax.div(n_pages + C - 1, C)
    lanes = pl.ds(pl.multiple_of(g * d, d), d)

    def entry(ci, j):
        return jnp.minimum(ci * C + j, width - 1)

    def page_copies(ci, buf, j):
        page = pages_ref[row, entry(ci, j)]
        return (pltpu.make_async_copy(k_hbm.at[page, :, lanes],
                                      kbuf.at[buf, j], ksem.at[buf, j]),
                pltpu.make_async_copy(v_hbm.at[page, :, lanes],
                                      vbuf.at[buf, j], vsem.at[buf, j]))

    def start_chunk(ci, buf):
        for j in range(C):  # static unroll: C independent page DMAs

            @pl.when(ci * C + j < n_pages)
            def _(j=j):
                for copy in page_copies(ci, buf, j):
                    copy.start()

            @pl.when(ci * C + j >= n_pages)
            def _zero(j=j):
                # (a weight of exactly 0 times garbage may be NaN)
                vbuf[buf, j] = jnp.zeros_like(vbuf[buf, j])
                kbuf[buf, j] = jnp.zeros_like(kbuf[buf, j])

    def wait_chunk(ci, buf):
        for j in range(C):

            @pl.when(ci * C + j < n_pages)
            def _(j=j):
                for copy in page_copies(ci, buf, j):
                    copy.wait()

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _first():
        start_chunk(0, 0)

    def chunk(ci, carry):
        buf = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _prefetch():
            start_chunk(ci + 1, 1 - buf)

        wait_chunk(ci, buf)
        k = kbuf[buf].reshape(C * ps, d)
        s = jax.lax.dot_general(
            q_ref[0, 0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, C*ps]
        at = jax.lax.broadcasted_iota(jnp.int32, (1, C * ps), 1)
        # each entry's count of valid tokens (0 past the entries in use)
        valid = jnp.zeros((1, C * ps), jnp.int32)
        for j in range(C):
            count = jnp.where(ci * C + j < n_pages,
                              counts_ref[row, entry(ci, j)], 0)
            valid = jnp.where(at // ps == j, count, valid)
        s = jnp.where(at % ps < valid, s, NEG_INF)
        m_prev = m_scr[...]  # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = vbuf[buf].reshape(C * ps, d)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [G, D]
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
        o_ref.dtype)


def sparse_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                  pages: jax.Array, counts: jax.Array, used: jax.Array,
                  scale: Optional[float] = None,
                  pages_per_chunk: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Pallas decode attention over listed pages: q [B,H,D], the query heads
    of KV head g over the first `used[b, g]` of `pages[b, g]` (physical
    pages of [P,ps,HK*D] pools, in any order), the first `counts[b, g, j]`
    tokens of entry j -> [B,H,D]. Grid (B, HK); `_sparse_decode_kernel` has
    the pipeline. `pages_per_chunk` defaults to what KV_CHUNK_VMEM_BYTES
    holds of one KV head's lanes of these pages, twice for K and for V."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    _, ps, width = k_pages.shape
    hk = width // d
    entries = pages.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if pages_per_chunk is None:
        page_bytes = ps * d * k_pages.dtype.itemsize
        pages_per_chunk = max(1, KV_CHUNK_VMEM_BYTES // (4 * page_bytes))
    C = min(pages_per_chunk, entries)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = pl.BlockSpec((1, 1, h // hk, d), lambda bi, gi, *_: (bi, gi, 0, 0))
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hk),
            in_specs=[group, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=group,
            scratch_shapes=[
                pltpu.VMEM((2, C, ps, d), k_pages.dtype),
                pltpu.VMEM((2, C, ps, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.SemaphoreType.DMA((2, C)),
                pltpu.VMEM((h // hk, 1), jnp.float32),
                pltpu.VMEM((h // hk, 1), jnp.float32),
                pltpu.VMEM((h // hk, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, h // hk, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="sparse_decode",
    )(pages.reshape(b * hk, entries), counts.reshape(b * hk, entries),
      used.reshape(b * hk), q.reshape(b, hk, h // hk, d), k_pages, v_pages)
    return out.reshape(b, h, d)


def listed_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     pages: jax.Array, counts: jax.Array, used: jax.Array,
                     scale: Optional[float] = None,
                     use_kernel: Optional[bool] = None) -> jax.Array:
    """`sparse_decode`'s result: on a TPU the kernel, elsewhere the listed
    pages gathered whole and a plain softmax over their valid tokens."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return sparse_decode(q, k_pages, v_pages, pages, counts, used, scale)
    b, h, d = q.shape
    _, ps, width = k_pages.shape
    hk, entries = width // d, pages.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def own_lanes(pool):  # [B,HK,L,ps,HK*D] -> KV head g's lanes of row g
        rows = jnp.take(pool, pages, axis=0).reshape(
            b, hk, entries * ps, hk, d).astype(jnp.float32)
        return jnp.stack([rows[:, g, :, g] for g in range(hk)], axis=1)

    k, v = own_lanes(k_pages), own_lanes(v_pages)              # [B,HK,T,D]
    valid = (jnp.arange(ps) < counts[..., None]) & (
        jnp.arange(entries)[:, None] < used[..., None, None])
    logits = jnp.einsum("bgqd,bgkd->bgqk", q.astype(jnp.float32).reshape(
        b, hk, h // hk, d), k, precision=jax.lax.Precision.HIGHEST) * scale
    logits = jnp.where(valid.reshape(b, hk, 1, entries * ps), logits,
                       NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bgqk,bgkd->bgqd", probs, v,
                      precision=jax.lax.Precision.HIGHEST).reshape(
        b, h, d).astype(q.dtype)


def sparse_write_attend(q: jax.Array, k: jax.Array, v: jax.Array, cache,
                        page_table: jax.Array, positions: jax.Array,
                        write_mask: jax.Array, seq_lens: jax.Array, sizes,
                        scale: Optional[float] = None):
    """One sparse-attention layer's decode step over its (k_pages, v_pages,
    m_pages): the token's k and v [B,1,HK,D] written at `positions` [B,1]
    where `write_mask` allows, the segment mean its key completes (if any),
    the pages chosen (`select_pages`), and q [B,1,H,D] attended over them.
    Returns (out [B,1,H,D], the cache, load [2] = pages selected and
    visible)."""
    k_pages, v_pages, m_pages = cache
    k_pages = paged_write(k_pages, k, page_table, positions, write_mask)
    v_pages = paged_write(v_pages, v, page_table, positions, write_mask)
    m_pages = index_step(m_pages, k_pages, page_table, positions[:, 0],
                         write_mask[:, 0])
    pages, counts, used, load = select_pages(
        q[:, 0], m_pages, page_table, seq_lens, sizes, scale,
        active=write_mask[:, 0])
    out = listed_attention(q[:, 0], k_pages, v_pages, pages, counts, used,
                           scale)
    return out[:, None], (k_pages, v_pages, m_pages), load
