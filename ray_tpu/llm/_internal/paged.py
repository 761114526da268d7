"""The engine's bookkeeping for the paged K/V pool: its size, which pages a
slot holds, and the index of shared prompt prefixes. Host only: nothing here
enters a traced program. The pool on the device (layout, scatter, gather,
attention, the Pallas decode kernel) is ray_tpu/ops/paged_attention.py.
"""

from __future__ import annotations

import dataclasses
from typing import List


@dataclasses.dataclass
class PagedCacheConfig:
    num_pages: int
    page_size: int = 16
    max_seqs: int = 8
    max_pages_per_seq: int = 64

    @property
    def max_context(self) -> int:
        return self.page_size * self.max_pages_per_seq


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
