"""Paged KV cache primitives (reference: ray.llm delegates paging to vLLM's
CUDA PagedAttention — here we ARE the engine, SURVEY §7.3).

TPU-first design: everything is static-shaped for XLA —
- pages:      [kv_heads, num_pages, page_size, head_dim] per layer (kv-head
  major so Pallas blocks tile the (page_size, head_dim) minor dims),
- page_table: [max_seqs, max_pages_per_seq] int32 (host-managed allocator),
- seq_lens:   [max_seqs] int32.
Writes are vectorized scatters (`.at[...].set(mode="drop")` — padding lanes
are sent out-of-bounds and dropped, so no dynamic shapes anywhere). The
decode gather reads each sequence's pages back as a contiguous view.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, NamedSharding, PartitionSpec

NEG_INF = -1e30


@dataclasses.dataclass
class PagedCacheConfig:
    num_pages: int
    page_size: int = 16
    max_seqs: int = 8
    max_pages_per_seq: int = 64

    @property
    def max_context(self) -> int:
        return self.page_size * self.max_pages_per_seq


def pages_spec(shape: Tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """How pages [HK, P, ps, D] lie on a mesh: split over kv heads, or
    replicated where the tensor axis does not divide them (tiny test
    models). The cache and the decode kernel's shard_map both use it."""
    from ray_tpu.parallel.sharding import spec_for_shape

    return spec_for_shape(("kv_heads", None, None, None), shape, mesh)


def init_paged_cache(cfg: PagedCacheConfig, num_layers: int, kv_heads: int,
                     head_dim: int, dtype=jnp.bfloat16,
                     mesh: Optional[Mesh] = None):
    """Per-layer (k_pages, v_pages) list, layout [HK, P, ps, D]; with a
    mesh, created directly in their sharding (no device holds them all)."""
    shape = (kv_heads, cfg.num_pages, cfg.page_size, head_dim)
    sharding = (NamedSharding(mesh, pages_spec(shape, mesh))
                if mesh is not None else None)
    return [(jnp.zeros(shape, dtype, device=sharding),
             jnp.zeros(shape, dtype, device=sharding))
            for _ in range(num_layers)]


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


class PageAllocator:
    """Host-side page bookkeeping with refcounts (the scheduler's half of
    paged attention; reference: vLLM BlockManager). A page may appear in
    several slots' page lists at once (prefix sharing) and is returned to
    the free list only when its last holder lets go. Shared pages are only
    ever FULL prompt pages, so no holder writes into them — sharing needs
    no copy-on-write (divergent suffixes land in fresh pages by position
    arithmetic)."""

    def __init__(self, cfg: PagedCacheConfig):
        self.cfg = cfg
        self.free = list(range(cfg.num_pages))
        # slot -> list of page ids
        self.slot_pages: List[List[int]] = [[] for _ in range(cfg.max_seqs)]
        self.ref: dict = {}  # page id -> holder count

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.cfg.page_size)

    def can_allocate(self, num_tokens: int) -> bool:
        return len(self.free) >= self.pages_needed(num_tokens)

    def share(self, slot: int, pages: List[int]) -> None:
        """Append already-allocated pages to slot's list (prefix reuse)."""
        for p in pages:
            self.ref[p] = self.ref.get(p, 0) + 1
        self.slot_pages[slot].extend(pages)

    def adopt(self, slot: int, pages: List[int]) -> None:
        """Like share(), but the caller already holds a ref per page (a
        pin taken with retain()) and transfers it to the slot."""
        self.slot_pages[slot].extend(pages)

    def retain(self, page: int) -> None:
        self.ref[page] = self.ref.get(page, 0) + 1

    def unref(self, page: int) -> None:
        n = self.ref.get(page, 0) - 1
        if n <= 0:
            self.ref.pop(page, None)
            self.free.append(page)
        else:
            self.ref[page] = n

    def ensure(self, slot: int, num_tokens: int) -> List[int]:
        """Grow slot's page list to cover num_tokens. Returns the page list.
        Raises if out of pages (caller preempts/queues/evicts)."""
        need = self.pages_needed(num_tokens)
        pages = self.slot_pages[slot]
        while len(pages) < need:
            if not self.free:
                raise MemoryError("out of KV cache pages")
            p = self.free.pop()
            self.ref[p] = self.ref.get(p, 0) + 1
            pages.append(p)
        return pages

    def release(self, slot: int) -> None:
        for p in self.slot_pages[slot]:
            self.unref(p)
        self.slot_pages[slot] = []

    @property
    def num_free(self) -> int:
        return len(self.free)


class PrefixCache:
    """Hash-chained full-page prefix index (reference: the prefix reuse
    vLLM provides under ray.llm's prefix-aware router — here native).

    Key for page i of a prompt: sha1(key[i-1] || tokens[i*ps:(i+1)*ps]),
    so a lookup can only match a contiguous prefix run. The cache holds
    one allocator ref per indexed page; eviction (LRU) drops entries whose
    pages no live sequence shares."""

    def __init__(self, allocator: PageAllocator):
        from collections import OrderedDict

        self._alloc = allocator
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()
        self.lookups = 0
        self.hit_pages = 0

    @staticmethod
    def page_digests(prompt_ids, page_size: int) -> List[bytes]:
        import hashlib

        import numpy as np

        n_full = len(prompt_ids) // page_size
        digests = []
        prev = b""
        arr = np.asarray(prompt_ids[:n_full * page_size], np.int32)
        for i in range(n_full):
            h = hashlib.sha1(prev)
            h.update(arr[i * page_size:(i + 1) * page_size].tobytes())
            prev = h.digest()
            digests.append(prev)
        return digests

    def match(self, digests: List[bytes]) -> List[int]:
        """Longest cached prefix run → page ids (refreshes LRU order)."""
        self.lookups += 1
        pages = []
        for d in digests:
            page = self._entries.get(d)
            if page is None:
                break
            self._entries.move_to_end(d)
            pages.append(page)
        self.hit_pages += len(pages)
        return pages

    def insert(self, digests: List[bytes], pages: List[int]) -> None:
        for d, p in zip(digests, pages):
            if d not in self._entries:
                self._alloc.retain(p)
                self._entries[d] = p

    def evict(self, n_pages: int) -> int:
        """Free up to n_pages cache-only pages (LRU first). Pages still
        shared by running sequences stay indexed."""
        freed = 0
        for d in list(self._entries):
            if freed >= n_pages:
                break
            p = self._entries[d]
            if self._alloc.ref.get(p, 0) == 1:  # only the cache holds it
                del self._entries[d]
                self._alloc.unref(p)
                freed += 1
        return freed

    def __len__(self) -> int:
        return len(self._entries)
