"""Distributed reference counting (ownership model).

Counterpart of src/ray/core_worker/reference_count.h:73 — the borrowing
protocol. Re-expressed compactly: every ObjectRef has exactly one *owner* (the
worker that created it). Local refcounts are driven by ObjectRef
construction/__del__; deserializing a ref registers a borrow which is reported
to the owner in batches. The owner frees the value (memory store + shm) when
its local count is zero and no borrowers remain.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Set, Tuple

from ray_tpu._private.ids import ObjectID


class _Record:
    __slots__ = ("local", "owned", "borrowers", "pinned_in_shm",
                 "owner_address")

    def __init__(self, owned: bool):
        self.local = 0
        self.owned = owned
        self.borrowers: Set[Tuple[str, int]] = set()
        self.pinned_in_shm = False
        self.owner_address: Optional[Tuple[str, int]] = None


class ReferenceCounter:
    def __init__(self, on_zero: Optional[Callable[[ObjectID], None]] = None):
        self._records: Dict[ObjectID, _Record] = {}
        # Re-entrant: allocating a `_Record` under the lock can start a
        # garbage collection that frees an ObjectRef, whose `__del__` comes
        # back through `remove_local_ref` on this thread.
        self._lock = threading.RLock()
        self._on_zero = on_zero
        # Ordered add/remove borrow reports per remote owner. Order matters:
        # a remove followed by a re-borrow's add must reach the owner in that
        # sequence or the owner could free under a live borrower.
        self._pending_borrow_reports: Dict[Tuple[str, int],
                                           list] = {}

    def add_owned_ref(self, object_id: ObjectID) -> None:
        with self._lock:
            rec = self._records.setdefault(object_id, _Record(owned=True))
            rec.owned = True
            rec.local += 1

    def add_local_ref(self, object_id: ObjectID) -> None:
        with self._lock:
            rec = self._records.setdefault(object_id, _Record(owned=False))
            rec.local += 1

    def add_borrowed_ref(self, ref) -> None:
        self.add_borrowed_refs((ref,))

    def add_borrowed_refs(self, refs) -> None:
        """Bulk borrow registration: one lock acquisition for a whole
        deserialized value (a get of 10k refs would otherwise pay
        lock+report bookkeeping 10k times)."""
        with self._lock:
            records = self._records
            reports = self._pending_borrow_reports
            for ref in refs:
                rec = records.get(ref.id)
                if rec is None:
                    rec = records[ref.id] = _Record(owned=False)
                rec.local += 1
                if ref.owner_address is not None:
                    addr = tuple(ref.owner_address)
                    rec.owner_address = addr
                    reports.setdefault(addr, []).append(("add", ref.id))
        for ref in refs:
            ref._registered = True

    def add_borrower(self, object_id: ObjectID, borrower: Tuple[str, int]) -> None:
        """Owner side: a remote worker now holds a reference."""
        with self._lock:
            rec = self._records.setdefault(object_id, _Record(owned=True))
            rec.borrowers.add(tuple(borrower))

    def remove_borrower(self, object_id: ObjectID, borrower: Tuple[str, int]) -> None:
        fire = False
        with self._lock:
            rec = self._records.get(object_id)
            if rec is None:
                return
            rec.borrowers.discard(tuple(borrower))
            fire = rec.owned and rec.local <= 0 and not rec.borrowers
        if fire and self._on_zero:
            self._on_zero(object_id)

    def remove_local_ref(self, object_id: ObjectID) -> None:
        fire = False
        with self._lock:
            rec = self._records.get(object_id)
            if rec is None:
                return
            rec.local -= 1
            if rec.local <= 0:
                if rec.owned and not rec.borrowers:
                    fire = True
                    del self._records[object_id]
                elif not rec.owned:
                    if rec.owner_address is not None:
                        # Last local ref to a borrowed object: tell the owner
                        # (the half of the protocol that was missing — the
                        # owner-side handler existed with zero callers).
                        self._pending_borrow_reports.setdefault(
                            rec.owner_address, []).append(
                                ("remove", object_id))
                    del self._records[object_id]
        if fire and self._on_zero:
            self._on_zero(object_id)

    def drain_borrow_reports(self) -> Dict[Tuple[str, int], list]:
        with self._lock:
            out = self._pending_borrow_reports
            self._pending_borrow_reports = {}
            return out

    def requeue_borrow_reports(self, owner: Tuple[str, int],
                               ops: list) -> None:
        """Put back a batch whose send failed, ahead of anything queued since
        (order is part of the protocol)."""
        with self._lock:
            existing = self._pending_borrow_reports.get(owner, [])
            self._pending_borrow_reports[owner] = list(ops) + existing

    def holds_local_ref(self, object_id: ObjectID) -> bool:
        with self._lock:
            rec = self._records.get(object_id)
            return rec is not None and rec.local > 0

    def borrower_snapshot(self) -> Dict[Tuple[str, int], Set[ObjectID]]:
        """Owner side: current borrowers per address (for the audit loop)."""
        out: Dict[Tuple[str, int], Set[ObjectID]] = {}
        with self._lock:
            for oid, rec in self._records.items():
                if rec.owned:
                    for b in rec.borrowers:
                        out.setdefault(b, set()).add(oid)
        return out

    def num_records(self) -> int:
        with self._lock:
            return len(self._records)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "records": len(self._records),
                "owned": sum(1 for r in self._records.values() if r.owned),
            }

    def summary(self) -> dict:
        """Ref-count debugging view (reference: `ray memory` — per-object
        local counts, ownership, borrowers)."""
        with self._lock:
            owned = borrowed = 0
            entries = []
            for oid, rec in self._records.items():
                if rec.owned:
                    owned += 1
                else:
                    borrowed += 1
                entries.append({
                    "object_id": oid.hex(),
                    "owned": rec.owned,
                    "local_refs": rec.local,
                    "borrowers": len(getattr(rec, "borrowers", ()) or ()),
                })
            return {"owned": owned, "borrowed": borrowed,
                    "entries": entries}
