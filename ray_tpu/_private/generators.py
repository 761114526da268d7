"""Streaming generators: num_returns="dynamic" (reference:
python/ray/_raylet.pyx:288 `ObjectRefGenerator`,
src/ray/core_worker/task_manager.h:168 `ReportGeneratorItemReturns`).

Redesign: the executor streams each yielded value to the owner as its own
object over a dedicated RPC (`report_generator_item`), awaiting each report —
the await IS the transport backpressure — and additionally pausing while the
owner reports more than `generator_backpressure_num_objects` unconsumed
items. Item object IDs are the task's return-ID sequence, so the owner-side
store, borrow protocol, and `ray.get` work on them unchanged."""

from __future__ import annotations

import asyncio
from typing import Dict, Optional


class GeneratorState:
    """Owner-side progress of one streaming task."""

    __slots__ = ("count", "reported", "consumed", "event", "landed",
                 "held_ns", "held_max_ns", "starved_ns")

    def __init__(self):
        self.count: Optional[int] = None  # total items, known at end
        self.reported = 0  # items the executor has shipped
        self.consumed = 0  # items the local consumer has pulled
        self.event = asyncio.Event()
        # With the flight recorder on (all on the owner's loop, one clock):
        # when each unconsumed item landed, how long items lay here before
        # the consumer asked (its sum and its worst), and how long the
        # consumer waited for items that had not landed.
        self.landed: Dict[int, int] = {}
        self.held_ns = 0
        self.held_max_ns = 0
        self.starved_ns = 0

    def pulse(self) -> None:
        self.event.set()
        self.event = asyncio.Event()

    async def wait(self) -> None:
        await self.event.wait()


class ObjectRefGenerator:
    """Iterator of ObjectRefs produced by a num_returns="dynamic" task.

    Both sync and async iteration are supported; each item is an ObjectRef
    that resolves independently (blocks materialize lazily via ray.get)."""

    def __init__(self, task_id, worker):
        self._task_id = task_id
        self._worker = worker
        self._idx = 0

    def __iter__(self):
        return self

    def __next__(self):
        oid = self._worker.loop_thread.run(
            self._worker.gen_next(self._task_id, self._idx))
        if oid is None:
            raise StopIteration
        self._idx += 1
        from ray_tpu._private.object_ref import ObjectRef

        return ObjectRef(oid, owner_address=self._worker.address)

    def __aiter__(self):
        return self

    async def __anext__(self):
        oid = await self._worker.gen_next(self._task_id, self._idx)
        if oid is None:
            raise StopAsyncIteration
        self._idx += 1
        from ray_tpu._private.object_ref import ObjectRef

        return ObjectRef(oid, owner_address=self._worker.address)

    def completed_length(self) -> Optional[int]:
        st = self._worker._generators.get(self._task_id)
        return st.count if st else None

    def __reduce__(self):
        raise TypeError(
            "ObjectRefGenerator cannot be pickled; pass the refs it yields")

    def __repr__(self):
        return f"ObjectRefGenerator({self._task_id})"
