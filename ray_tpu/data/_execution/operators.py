"""Physical operators: InputDataBuffer, task-pool and actor-pool map
operators, OutputSplitter.

Reference: python/ray/data/_internal/execution/operators/
(input_data_buffer.py, task_pool_map_operator.py,
actor_pool_map_operator.py, output_splitter.py). Redesign: map tasks
return ``(block, metadata)`` as two objects so the driver learns row
and byte counts from a tiny metadata get — never a payload pull — and
the byte counts feed the ExecutionBudget.store_bytes accounting.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.data._execution.autoscaler import PoolAutoscalerPolicy
from ray_tpu.data._execution.interfaces import PhysicalOperator, RefBundle
from ray_tpu.data.block import BlockMetadata
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class InputDataBuffer(PhysicalOperator):
    """Feeds the DAG. Driver-side sources (``_Source``) are pulled one
    block per launch and put into the store with exact metadata;
    pre-materialized refs (``_RefSource``) stream through with unknown
    sizes (counting 0 toward the byte budget — those blocks are already
    resident, the budget governs what this execution *adds*)."""

    def __init__(self, source: Any, rm: Any):
        super().__init__(getattr(source, "name", "Input"), window=4,
                         max_outqueue=4)
        self._source = source
        self._rm = rm
        self._iter = None
        self._ref_iter = None
        self._exhausted = False
        self.inputs_done = True  # nothing upstream of an input buffer

    def _ensure_started(self) -> None:
        if self._iter is not None or self._ref_iter is not None:
            return
        # _RefSource thunks (shuffle/repartition/join) resolve here —
        # lazily, on first pull (the iterator may itself be a nested
        # streaming execution).
        if hasattr(self._source, "resolve_refs"):
            self._ref_iter = iter(self._source.resolve_refs())
        else:
            self._iter = self._source.make_blocks()

    def can_launch(self) -> bool:
        return (not self._exhausted
                and len(self.outqueue) < self.max_outqueue)

    def launch_one(self) -> None:
        import ray_tpu

        self._ensure_started()
        if self._ref_iter is not None:
            try:
                self._emit(RefBundle(next(self._ref_iter)))
            except StopIteration:
                self._exhausted = True
            return
        try:
            block = next(self._iter)
        except StopIteration:
            self._exhausted = True
            return
        meta = BlockMetadata.of(block)
        bundle = RefBundle(ray_tpu.put(block), num_rows=meta.num_rows,
                           size_bytes=meta.size_bytes)
        self._rm.on_bytes_acquired(bundle.bytes_or(0))
        self._emit(bundle)

    def exhausted(self) -> bool:
        return self._exhausted


class _MapOperatorBase(PhysicalOperator):
    """Shared machinery for task/actor map operators: ordered emission
    (results surface in input order), tiny-metadata harvesting, and
    budget byte accounting."""

    is_map = True

    def __init__(self, name: str, rm: Any, **kw):
        super().__init__(name, **kw)
        self._rm = rm
        self._next_idx = 0       # submission order
        self._emit_idx = 0       # next index owed to the outqueue
        # idx -> {"out": ref, "meta": ref, "in": RefBundle, ...}
        self._pending: Dict[int, Dict[str, Any]] = {}
        # idx -> RefBundle completed but waiting for earlier indices
        self._ready: Dict[int, RefBundle] = {}

    def num_inflight(self) -> int:
        return len(self._pending)

    def pending_outputs(self) -> int:
        return len(self._pending) + len(self._ready)

    def can_launch(self) -> bool:
        return bool(self.inqueue)

    def _track(self, out_ref: Any, meta_ref: Any, in_bundle: RefBundle,
               **extra: Any) -> None:
        entry = {"out": out_ref, "meta": meta_ref, "in": in_bundle}
        entry.update(extra)
        self._pending[self._next_idx] = entry
        self._next_idx += 1
        self._rm.on_launch(self)
        self.peak_inflight = max(self.peak_inflight, len(self._pending))

    def meta_refs(self) -> List[Any]:
        return [e["meta"] for e in self._pending.values()]

    def poll(self) -> bool:
        if not self._pending:
            return False
        import ray_tpu

        metas = [e["meta"] for e in self._pending.values()]
        ready, _ = ray_tpu.wait(metas, num_returns=len(metas), timeout=0)
        if not ready:
            return False
        ready_ids = {r.id.binary() for r in ready}
        progressed = False
        for idx in sorted(self._pending):
            e = self._pending[idx]
            if e["meta"].id.binary() not in ready_ids:
                continue
            del self._pending[idx]
            self._on_task_done(e)
            try:
                meta = ray_tpu.get(e["meta"])
                bundle = RefBundle(e["out"], num_rows=meta["rows"],
                                   size_bytes=meta["bytes"])
            except Exception:  # noqa: BLE001 - the task raised: the error
                # value is stored in the block ref too, so it surfaces to
                # the consumer on get.
                bundle = RefBundle(e["out"])
            self._rm.on_complete(self)
            # The input block ref is dropped with this entry: its bytes
            # leave the execution's resident set, the output's enter.
            self._rm.on_bytes_released(e["in"].bytes_or(0))
            self._rm.on_bytes_acquired(bundle.bytes_or(0))
            self._ready[idx] = bundle
            progressed = True
        while self._emit_idx in self._ready:
            self._emit(self._ready.pop(self._emit_idx))
            self._emit_idx += 1
        return progressed

    def _on_task_done(self, entry: Dict[str, Any]) -> None:
        pass

    def exhausted(self) -> bool:
        return (self.inputs_done and not self.inqueue
                and not self._pending and not self._ready)


class TaskPoolMapOperator(_MapOperatorBase):
    """Stateless transform: one ray_tpu task per block (reference:
    task_pool_map_operator.py)."""

    def __init__(self, logical_op: Any, rm: Any):
        super().__init__(getattr(logical_op, "name", "MapBatches"), rm,
                         num_cpus=getattr(logical_op, "num_cpus", 1.0),
                         window=getattr(logical_op, "window", 4))
        self._logical = logical_op
        import ray_tpu

        @ray_tpu.remote
        def _run(block, op=logical_op):
            from ray_tpu.data.dataset import _apply_map_batches

            out = _apply_map_batches(op, block)
            m = BlockMetadata.of(out)
            return out, {"rows": m.num_rows, "bytes": m.size_bytes}

        self._remote = _run.options(num_cpus=self.num_cpus, num_returns=2)

    def launch_one(self) -> None:
        bundle = self.inqueue.popleft()
        out_ref, meta_ref = self._remote.remote(bundle.ref)
        self._track(out_ref, meta_ref, bundle)


class ActorPoolMapOperator(_MapOperatorBase):
    """Stateful transform over an autoscaling pool of actors (reference:
    actor_pool_map_operator.py + autoscaler/default_autoscaler.py). The
    expensive constructor runs once per actor; the pool grows on
    sustained input-queue depth and drains back (idle-first) when the
    queue empties."""

    def __init__(self, logical_op: Any, rm: Any,
                 on_scale_event: Optional[Callable[[str], None]] = None):
        min_size = max(1, int(getattr(logical_op, "concurrency", 1)))
        max_size = max(min_size,
                       int(getattr(logical_op, "max_concurrency", None)
                           or min_size))
        per_actor = max(1, int(getattr(logical_op, "window_per_actor", 2)))
        # The ``window`` property below reads these — set them before the
        # base __init__ touches self.window.
        self._per_actor = per_actor
        self._pool: List[Dict[str, Any]] = []  # [{"handle", "inflight"}]
        super().__init__(
            getattr(logical_op, "name", "MapBatches(actors)"), rm,
            num_cpus=getattr(logical_op, "num_cpus", 1.0),
            window=max_size * per_actor,
            max_inqueue=max(4, 2 * per_actor * max_size),
            max_outqueue=max(2, per_actor * max_size))
        self._logical = logical_op
        self._policy = PoolAutoscalerPolicy(
            min_size, max_size,
            getattr(logical_op, "autoscale_config", None))
        self._on_scale_event = on_scale_event or (lambda direction: None)
        self.pool_size_peak = 0
        self.scale_ups = 0
        self.scale_downs = 0
        self._actor_cls = self._build_actor_cls()
        for _ in range(min_size):
            self._add_actor()

    # The backpressure chain reads ``window`` as the concurrency cap;
    # for a pool it is what the *current* pool can hold in flight.
    @property
    def window(self) -> int:
        return max(1, len(self._pool) * self._per_actor)

    @window.setter
    def window(self, value: int) -> None:
        pass  # base-class __init__ assignment; pool size is the truth

    def _build_actor_cls(self):
        import ray_tpu
        from ray_tpu.data.block import (
            block_as_format,
            block_concat,
            iter_block_batches,
            normalize_batch_output,
        )

        op = self._logical
        cls, batch_size = op.cls, op.batch_size
        fn_kwargs = op.fn_kwargs or {}
        fmt = op.batch_format
        ctor_args = op.fn_constructor_args
        ctor_kwargs = op.fn_constructor_kwargs or {}

        @ray_tpu.remote
        class _BatchWorker:
            def __init__(self):
                self.inst = cls(*ctor_args, **ctor_kwargs)

            def run(self, block):
                outs = []
                for batch in iter_block_batches(block, batch_size):
                    outs.append(normalize_batch_output(
                        self.inst(block_as_format(batch, fmt),
                                  **fn_kwargs)))
                out = block_concat(outs) if outs else {}
                m = BlockMetadata.of(out)
                return out, {"rows": m.num_rows, "bytes": m.size_bytes}

        return _BatchWorker.options(
            num_cpus=op.num_cpus,
            num_tpus=getattr(op, "num_tpus", 0.0))

    def _add_actor(self) -> None:
        self._pool.append({"handle": self._actor_cls.remote(),
                           "inflight": 0})
        self.pool_size_peak = max(self.pool_size_peak, len(self._pool))

    def pool_size(self) -> int:
        return len(self._pool)

    def idle_actors(self) -> int:
        return sum(1 for a in self._pool if a["inflight"] == 0)

    def can_launch(self) -> bool:
        return bool(self.inqueue) and any(
            a["inflight"] < self._per_actor for a in self._pool)

    def launch_one(self) -> None:
        bundle = self.inqueue.popleft()
        slot = min((a for a in self._pool
                    if a["inflight"] < self._per_actor),
                   key=lambda a: a["inflight"])
        slot["inflight"] += 1
        out_ref, meta_ref = slot["handle"].run.options(
            num_returns=2).remote(bundle.ref)
        self._track(out_ref, meta_ref, bundle, slot=slot)

    def _on_task_done(self, entry: Dict[str, Any]) -> None:
        slot = entry.get("slot")
        if slot is not None and slot["inflight"] > 0:
            slot["inflight"] -= 1

    def maybe_autoscale(self, now: float) -> None:
        delta = self._policy.tick(now, queued=len(self.inqueue),
                                  pool_size=len(self._pool),
                                  idle=self.idle_actors())
        if delta > 0:
            for _ in range(delta):
                self._add_actor()
            self.scale_ups += 1
            self._on_scale_event("up")
            logger.debug("data actor pool %s scaled up to %d",
                         self.name, len(self._pool))
        elif delta < 0:
            import ray_tpu

            killed = 0
            for slot in [a for a in self._pool if a["inflight"] == 0]:
                if killed >= -delta:
                    break
                self._pool.remove(slot)
                try:
                    ray_tpu.kill(slot["handle"])
                except Exception:  # noqa: BLE001
                    pass
                killed += 1
            if killed:
                self.scale_downs += 1
                self._on_scale_event("down")
                logger.debug("data actor pool %s drained down to %d",
                             self.name, len(self._pool))

    def shutdown(self) -> None:
        import ray_tpu

        for slot in self._pool:
            try:
                ray_tpu.kill(slot["handle"])
            except Exception:  # noqa: BLE001
                pass
        self._pool.clear()

    def stat_row(self) -> Dict[str, Any]:
        row = super().stat_row()
        row.update({
            "pool_size": len(self._pool),
            "pool_size_peak": self.pool_size_peak,
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
        })
        return row


class OutputSplitter(PhysicalOperator):
    """Deals finished bundles round-robin to N consumer queues
    (reference: output_splitter.py behind streaming_split). Dealt
    bundles leave the execution's byte accounting — the per-split
    queues are consumer-owned buffers, and blocking the deal on one
    slow split would deadlock the others (the round-robin contract
    means every split's next block may sit behind a block owed to a
    slower split)."""

    def __init__(self, n: int, rm: Any):
        super().__init__(f"OutputSplitter({n})", window=1)
        self.n = max(1, int(n))
        self._rm = rm
        self.split_queues: List[List[RefBundle]] = [[] for _ in range(self.n)]
        self._rr = 0

    def can_accept_input(self) -> bool:
        return True  # dealing is unbounded; see class docstring

    def poll(self) -> bool:
        progressed = False
        while self.inqueue:
            bundle = self.inqueue.popleft()
            self.split_queues[self._rr].append(bundle)
            self._rr = (self._rr + 1) % self.n
            self._rm.on_bytes_released(bundle.bytes_or(0))
            self.blocks_out += 1
            if bundle.num_rows is not None:
                self.rows_out += bundle.num_rows
            progressed = True
        return progressed


def estimate_output_rate(op: PhysicalOperator,
                         started_at: float) -> float:
    dt = max(1e-6, time.monotonic() - started_at)
    return op.rows_out / dt
