"""Dataset: lazy logical plan + pull-based streaming execution over tasks.

Reference counterparts: python/ray/data/dataset.py:160 (`Dataset`),
_internal/execution/streaming_executor.py:52 (pull-based streaming executor
with backpressure), data/iterator.py (`iter_batches`, `streaming_split`).

Redesign notes (TPU-first, not a port):
- Blocks are numpy-dict columns (see block.py) — the zero-copy staging format
  for `jax.device_put`.
- The executor (data/_execution) is an operator DAG with bounded block-ref
  queues, pumped by the consumer's pull: an unpulled downstream fills its
  queues and the operators above it stop launching.
- Transforms run as ray_tpu tasks; block refs flow through the object store
  (shm, zero-copy on one node).
"""

from __future__ import annotations

import dataclasses
import builtins
import itertools
_range = builtins.range
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

import ray_tpu
from ray_tpu.data.block import (
    Block,
    BlockMetadata,
    VALUE_COL,
    block_concat,
    block_from_items,
    block_num_rows,
    block_select,
    block_slice,
    block_to_items,
    iter_block_batches,
    normalize_batch_output,
    as_arrow_block,
    as_numpy_block,
    as_pandas_batch,
    block_as_format,
    is_arrow_block,
)

DEFAULT_BLOCK_ROWS = 4096
DEFAULT_WINDOW = 4  # concurrent transform tasks per operator


# ---------------------------------------------------------------------------
# Logical ops
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Source:
    """Produces blocks driver-side, lazily."""

    make_blocks: Callable[[], Iterator[Block]]
    name: str = "Source"


@dataclasses.dataclass
class _RefSource:
    """Blocks already in the object store (materialized datasets), or a
    thunk producing their refs on first consumption (lazy all-to-all ops
    like hash_shuffle)."""

    refs: Any  # List[ObjectRef] | Callable[[], List[ObjectRef]]
    name: str = "RefSource"

    def resolve_refs(self) -> List[Any]:
        return self.refs() if callable(self.refs) else self.refs


@dataclasses.dataclass
class _MapBatches:
    fn: Optional[Callable]
    batch_size: Optional[int]
    num_cpus: float = 1.0
    window: int = DEFAULT_WINDOW
    name: str = "MapBatches"
    fn_kwargs: Optional[Dict[str, Any]] = None
    batch_format: Optional[str] = None  # None = numpy staging format
    # Set by _fuse_plan: a chain of map ops executed inside ONE task.
    fused_stages: Optional[List["_MapBatches"]] = None


@dataclasses.dataclass
class _MapBatchesActor:
    """Stateful transform: a pool of actors each holding one instance of
    `cls` (reference: ActorPoolMapOperator,
    _internal/execution/operators/actor_pool_map_operator.py). The expensive
    constructor (model load, engine init) runs once per actor, not per
    block."""

    cls: type
    batch_size: Optional[int]
    concurrency: int = 1
    num_cpus: float = 1.0
    num_tpus: float = 0.0
    window_per_actor: int = 2
    name: str = "MapBatches(actors)"
    fn_constructor_args: tuple = ()
    fn_constructor_kwargs: Optional[Dict[str, Any]] = None
    fn_kwargs: Optional[Dict[str, Any]] = None
    batch_format: Optional[str] = None
    # Autoscaling ceiling: `concurrency` is the floor the pool starts
    # at, `max_concurrency` what the executor's PoolAutoscalerPolicy may
    # grow it to under sustained input-queue depth. None = fixed pool.
    max_concurrency: Optional[int] = None


def _apply_map_batches(op: _MapBatches, block: Block) -> Block:
    for stage in op.fused_stages or [op]:
        outs = []
        kwargs = stage.fn_kwargs or {}
        fmt = getattr(stage, "batch_format", None)
        for batch in iter_block_batches(block, stage.batch_size):
            outs.append(normalize_batch_output(
                stage.fn(block_as_format(batch, fmt), **kwargs)))
        block = block_concat(outs) if outs else {}
    return block


# ---------------------------------------------------------------------------
# Plan optimization
# ---------------------------------------------------------------------------
def _fuse_plan(plan: List[Any]) -> List[Any]:
    """Plan optimization now runs through the rule framework
    (data/planner.py — reference: _internal/logical/optimizers.py);
    operator fusion is its first built-in rule. Kept as the executor's
    entry point so custom rules registered via planner.register_rule
    apply to every dataset."""
    from ray_tpu.data.planner import optimize

    return optimize(plan)


# ---------------------------------------------------------------------------
# Streaming execution
# ---------------------------------------------------------------------------
def _exec_stream(plan: List[Any]) -> Iterator[Any]:
    """Plan → iterator of Block ObjectRefs: the op-DAG streaming executor
    (data/_execution) — all operators run concurrently under the
    ExecutionBudget with output-queue-aware scheduling and actor-pool
    autoscaling."""
    from ray_tpu.data._execution import execute_plan

    return execute_plan(plan)


class Dataset:
    """Lazy dataset of columnar blocks (reference: data/dataset.py:160)."""

    def __init__(self, plan: List[Any]):
        self._plan = plan

    # -- transforms (lazy) ------------------------------------------------
    def map_batches(self, fn: Callable, *, batch_size: Optional[int] = None,
                    num_cpus: float = 1.0, num_tpus: float = 0.0,
                    concurrency: Any = DEFAULT_WINDOW,
                    batch_format: Optional[str] = None,
                    fn_constructor_args: tuple = (),
                    fn_constructor_kwargs: Optional[Dict[str, Any]] = None,
                    fn_kwargs: Optional[Dict[str, Any]] = None) -> "Dataset":
        """Function transforms run as tasks; a callable CLASS runs on a pool
        of `concurrency` stateful actors, constructed once each (reference:
        TaskPoolMapOperator vs ActorPoolMapOperator). For an actor class,
        ``concurrency=(min, max)`` enables autoscaling: the pool starts at
        `min` and the streaming executor grows it toward `max` on sustained
        input-queue depth, draining back (idle-first) when the queue
        empties. batch_format selects what `fn` sees: "numpy" (default;
        zero-copy views for Arrow-backed numeric columns), "pyarrow", or
        "pandas"."""
        max_concurrency: Optional[int] = None
        if isinstance(concurrency, (tuple, list)):
            if not isinstance(fn, type):
                raise ValueError(
                    "concurrency=(min, max) autoscaling requires a callable "
                    "class (actor pool); task-based map_batches takes an "
                    "int concurrency")
            lo, hi = concurrency
            if int(lo) < 1 or int(hi) < int(lo):
                raise ValueError(
                    f"bad concurrency range {concurrency!r}: need "
                    "1 <= min <= max")
            concurrency, max_concurrency = int(lo), int(hi)
        if isinstance(fn, type):
            return Dataset(self._plan + [_MapBatchesActor(
                fn, batch_size, concurrency=concurrency, num_cpus=num_cpus,
                num_tpus=num_tpus, name=f"MapBatches({fn.__name__})",
                fn_constructor_args=fn_constructor_args,
                fn_constructor_kwargs=fn_constructor_kwargs,
                fn_kwargs=fn_kwargs, batch_format=batch_format,
                max_concurrency=max_concurrency)])
        return Dataset(self._plan + [_MapBatches(
            fn, batch_size, num_cpus, concurrency,
            name=getattr(fn, "__name__", "map_batches"),
            fn_kwargs=fn_kwargs, batch_format=batch_format)])

    def map(self, fn: Callable, **opts) -> "Dataset":
        def _map_rows(batch: Block) -> Block:
            return block_from_items([fn(r) for r in block_to_items(batch)])

        return self.map_batches(_map_rows, **opts)

    def flat_map(self, fn: Callable, **opts) -> "Dataset":
        def _flat(batch: Block) -> Block:
            out: List[Any] = []
            for r in block_to_items(batch):
                out.extend(fn(r))
            return block_from_items(out)

        return self.map_batches(_flat, **opts)

    def filter(self, fn: Callable, **opts) -> "Dataset":
        def _filter(batch: Block) -> Block:
            mask = np.asarray([bool(fn(r)) for r in block_to_items(batch)])
            return block_select(batch, mask) if len(mask) else batch

        return self.map_batches(_filter, **opts)

    def add_column(self, name: str, fn: Callable, **opts) -> "Dataset":
        def _add(batch: Block) -> Block:
            out = dict(batch)
            out[name] = np.asarray(fn(batch))
            return out

        return self.map_batches(_add, **opts)

    def drop_columns(self, cols: Sequence[str], **opts) -> "Dataset":
        def _drop(batch: Block) -> Block:
            return {k: v for k, v in batch.items() if k not in cols}

        return self.map_batches(_drop, **opts)

    def select_columns(self, cols: Sequence[str], **opts) -> "Dataset":
        def _select(batch: Block) -> Block:
            return {k: batch[k] for k in cols}

        return self.map_batches(_select, **opts)

    # -- consumption ------------------------------------------------------
    def iter_block_refs(self) -> Iterator[Any]:
        return _exec_stream(self._plan)

    def iter_blocks(self) -> Iterator[Block]:
        for ref in self.iter_block_refs():
            yield ray_tpu.get(ref)

    def iter_batches(self, *, batch_size: Optional[int] = 256,
                     prefetch_batches: int = 1,
                     drop_last: bool = False,
                     batch_format: Optional[str] = "numpy"
                     ) -> Iterator[Block]:
        """Re-batched streaming iteration (reference: data/iterator.py).
        Arrow-backed blocks slice zero-copy; with the default
        batch_format="numpy", numeric null-free columns are yielded as
        zero-copy numpy views over the Arrow buffers."""
        for b in self._iter_batches_raw(batch_size=batch_size,
                                        drop_last=drop_last):
            yield block_as_format(b, batch_format)

    def _iter_batches_raw(self, *, batch_size: Optional[int],
                          drop_last: bool) -> Iterator[Block]:
        leftover: Optional[Block] = None
        for block in self.iter_blocks():
            if leftover is not None and block_num_rows(leftover):
                block = block_concat([leftover, block])
                leftover = None
            if batch_size is None:
                yield block
                continue
            n = block_num_rows(block)
            i = 0
            while n - i >= batch_size:
                yield block_slice(block, i, i + batch_size)
                i += batch_size
            if i < n:
                leftover = block_slice(block, i, n)
        if leftover is not None and block_num_rows(leftover) and not drop_last:
            yield leftover

    def iter_rows(self) -> Iterator[Any]:
        for block in self.iter_blocks():
            yield from block_to_items(block)

    def limit(self, n: int) -> "Dataset":
        """Lazy row-count truncation (stops pulling upstream once filled)."""
        parent = self

        def gen():
            remaining = n
            for block in parent.iter_blocks():
                if remaining <= 0:
                    return
                rows = block_num_rows(block)
                if rows <= remaining:
                    yield block
                    remaining -= rows
                else:
                    yield block_slice(block, 0, remaining)
                    return

        return Dataset([_Source(gen, name="Limit")])

    def iter_torch_batches(self, *, batch_size: Optional[int] = 256,
                           drop_last: bool = False) -> Iterator[Dict[str, Any]]:
        """Batches as torch tensors (reference: iter_torch_batches)."""
        import torch

        for batch in self.iter_batches(batch_size=batch_size,
                                       drop_last=drop_last):
            yield {k: torch.as_tensor(np.ascontiguousarray(v))
                   for k, v in batch.items()}

    def take(self, limit: int = 20) -> List[Any]:
        out: List[Any] = []
        for row in self.iter_rows():
            out.append(row)
            if len(out) >= limit:
                break
        return out

    def take_all(self) -> List[Any]:
        return list(self.iter_rows())

    def count(self) -> int:
        if isinstance(self._plan[0], _RefSource) and len(self._plan) == 1:
            return sum(ray_tpu.get(_remote_num_rows().remote(r))
                       for r in self._plan[0].resolve_refs())
        return sum(block_num_rows(b) for b in self.iter_blocks())

    def schema(self) -> Optional[Dict[str, Any]]:
        for block in self.iter_blocks():
            return BlockMetadata.of(block).schema
        return None

    def materialize(self) -> "Dataset":
        refs = list(self.iter_block_refs())
        return Dataset([_RefSource(refs)])

    def num_blocks(self) -> int:
        return len(self.materialize()._plan[0].refs)

    # -- reorganization ---------------------------------------------------
    # All three exchange ops run as distributed map/reduce task DAGs: the
    # driver routes ObjectRefs and small metadata (row counts, key
    # samples), never block payloads (reference:
    # data/_internal/execution/operators/hash_shuffle.py,
    # planner/exchange/sort_task_spec.py). A one-block upstream keeps the
    # trivial local path.
    def repartition(self, num_blocks: int) -> "Dataset":
        """Split/merge exchange: input blocks are sliced at the global row
        boundaries of the target layout, slices route to merge tasks."""
        N = max(1, int(num_blocks))
        plan = list(self._plan)

        def run() -> List[Any]:
            upstream = list(_exec_stream(plan))

            @ray_tpu.remote
            def _count(b: Block) -> int:
                return block_num_rows(b)

            counts = ray_tpu.get([_count.remote(r) for r in upstream])
            total = sum(counts)
            per = -(-total // N) if total else 1

            @ray_tpu.remote
            def _slices(block: Block, bounds: List[Tuple[int, int]]):
                return tuple(block_slice(block, lo, hi)
                             for lo, hi in bounds)

            @ray_tpu.remote
            def _merge(*parts: Block) -> Block:
                nonempty = [p for p in parts if block_num_rows(p)]
                return block_concat(nonempty) if nonempty else {}

            out_parts: List[List[Any]] = [[] for _ in _range(N)]
            offset = 0
            for ref, cnt in zip(upstream, counts):
                bounds = []
                owners = []
                pos = 0
                while pos < cnt:
                    out_idx = min((offset + pos) // per, N - 1)
                    hi = min(cnt, (out_idx + 1) * per - offset)
                    bounds.append((pos, hi))
                    owners.append(out_idx)
                    pos = hi
                if not bounds:
                    continue
                if len(bounds) == 1:
                    out_parts[owners[0]].append(ref)
                else:
                    parts = _slices.options(
                        num_returns=len(bounds)).remote(ref, bounds)
                    for own, part in zip(owners, parts):
                        out_parts[own].append(part)
                offset += cnt
            return [_merge.remote(*parts) if parts else _merge.remote()
                    for parts in out_parts]

        return Dataset([_RefSource(run, name="Repartition")])

    def random_shuffle(self, *, seed: Optional[int] = None) -> "Dataset":
        """Two-stage distributed shuffle: each block scatters its rows to
        P random partitions; each reduce merges and locally permutes —
        the composition is a uniform global shuffle with O(block) driver
        memory."""
        plan = list(self._plan)

        def run() -> List[Any]:
            upstream = list(_exec_stream(plan))
            P = len(upstream)
            if P <= 1:

                @ray_tpu.remote
                def _local_shuffle(b: Block, seed=seed) -> Block:
                    b = as_numpy_block(b)
                    n = block_num_rows(b)
                    perm = np.random.default_rng(seed).permutation(n)
                    return {k: np.asarray(v)[perm] for k, v in b.items()}

                return [_local_shuffle.remote(r) for r in upstream]

            @ray_tpu.remote
            def _scatter(block: Block, block_seed: int, P=P):
                block = as_numpy_block(block)
                rng = np.random.default_rng(block_seed)
                codes = rng.integers(0, P, block_num_rows(block))
                return tuple(
                    {k: np.asarray(v)[codes == p]
                     for k, v in block.items()}
                    for p in _range(P))

            @ray_tpu.remote
            def _merge_permute(part_seed: int, *parts: Block) -> Block:
                nonempty = [p for p in parts if block_num_rows(p)]
                merged = as_numpy_block(
                    block_concat(nonempty) if nonempty else {})
                n = block_num_rows(merged)
                perm = np.random.default_rng(part_seed).permutation(n)
                return {k: np.asarray(v)[perm] for k, v in merged.items()}

            root = np.random.default_rng(seed)
            seeds = [int(s) for s in
                     root.integers(0, 2**31 - 1, size=2 * P)]
            rows = [_scatter.options(num_returns=P).remote(u, seeds[i])
                    for i, u in enumerate(upstream)]
            return [_merge_permute.remote(seeds[P + p],
                                          *[row[p] for row in rows])
                    for p in _range(P)]

        return Dataset([_RefSource(run, name="RandomShuffle")])

    def sort(self, key: str, *, descending: bool = False) -> "Dataset":
        """Distributed range-partition sort: sample key quantiles (the
        only data the driver touches), partition every block by the
        boundaries, sort each range locally. Output blocks are globally
        ordered."""
        plan = list(self._plan)

        def run() -> List[Any]:
            upstream = list(_exec_stream(plan))
            P = len(upstream)

            @ray_tpu.remote
            def _sort_block(b: Block, key=key,
                            descending=descending) -> Block:
                b = as_numpy_block(b)
                order = np.argsort(np.asarray(b[key]), kind="stable")
                if descending:
                    order = order[::-1]
                return {k: np.asarray(v)[order] for k, v in b.items()}

            if P <= 1:
                return [_sort_block.remote(r) for r in upstream]

            @ray_tpu.remote
            def _sample(b: Block, key=key, k: int = 64):
                b = as_numpy_block(b)
                vals = np.sort(np.asarray(b[key]))
                if len(vals) == 0:
                    return vals
                idx = np.linspace(0, len(vals) - 1,
                                  min(k, len(vals))).astype(np.int64)
                return vals[idx]

            samples = [s for s in
                       ray_tpu.get([_sample.remote(r) for r in upstream])
                       if len(s)]
            if not samples:
                return list(upstream)
            merged = np.sort(np.concatenate(samples))
            # P-1 interior boundaries at the sample quantiles.
            q = np.linspace(0, len(merged) - 1, P + 1)[1:-1]
            bounds = merged[q.astype(np.int64)]

            @ray_tpu.remote
            def _range_part(block: Block, key=key, bounds=bounds, P=P):
                block = as_numpy_block(block)
                codes = np.searchsorted(bounds, np.asarray(block[key]),
                                        side="right")
                return tuple(
                    {k: np.asarray(v)[codes == p]
                     for k, v in block.items()}
                    for p in _range(P))

            @ray_tpu.remote
            def _sort_merge(key: str, descending: bool,
                            *parts: Block) -> Block:
                nonempty = [p for p in parts if block_num_rows(p)]
                merged = as_numpy_block(
                    block_concat(nonempty) if nonempty else {})
                if not block_num_rows(merged):
                    return merged
                order = np.argsort(np.asarray(merged[key]), kind="stable")
                if descending:
                    order = order[::-1]
                return {k: np.asarray(v)[order] for k, v in merged.items()}

            rows = [_range_part.options(num_returns=P).remote(u)
                    for u in upstream]
            parts = [_sort_merge.remote(key, descending,
                                        *[row[p] for row in rows])
                     for p in _range(P)]
            # Ascending ranges; descending output reverses the range order
            # (each range is already internally descending).
            return parts[::-1] if descending else parts

        return Dataset([_RefSource(run, name="Sort")])

    def groupby(self, key: str, *,
                num_partitions: Optional[int] = None) -> "GroupedData":
        """num_partitions=None aggregates driver-side (right at single-host
        block counts); num_partitions=P runs a distributed hash shuffle
        (reference: _internal/execution/operators/hash_shuffle.py) so each
        of P reduce blocks holds COMPLETE groups — aggregations then run as
        per-block tasks with no driver materialization."""
        if num_partitions:
            return GroupedData(self.hash_shuffle(key, num_partitions), key,
                               pre_partitioned=True)
        return GroupedData(self, key)

    def hash_shuffle(self, key: str, num_partitions: int) -> "Dataset":
        """All-to-all: partition every block by a stable hash of `key`,
        merge partition p across blocks into one output block. Map and
        reduce are cluster tasks; the driver only routes refs (reference:
        hash shuffle map/reduce tasks, operators/hash_shuffle.py). Lazy
        like every other operator: the shuffle submits when the result is
        first consumed."""
        P = max(1, int(num_partitions))
        plan = list(self._plan)

        def run_shuffle() -> List[Any]:
            upstream = list(_exec_stream(plan))

            @ray_tpu.remote
            def _merge(*blocks: Block) -> Block:
                nonempty = [b for b in blocks if block_num_rows(b)]
                return block_concat(nonempty) if nonempty else {}

            if P == 1:
                # Degenerate shuffle: everything lands in one partition —
                # no map stage needed (num_returns=1 would hand _merge a
                # 1-tuple, not a block).
                return [_merge.remote(*upstream)]

            @ray_tpu.remote
            def _partition(block: Block, key=key, P=P):
                block = as_numpy_block(block)
                if not block or not block_num_rows(block):
                    # empty upstream block (e.g. a filter that dropped
                    # everything): every partition gets its empty schema
                    empty = {k: np.asarray(v)[:0] for k, v in block.items()}
                    return tuple(dict(empty) for _ in _range(P))
                vals = block[key]
                codes = _stable_hash_codes(vals, P)
                return tuple(
                    {k: np.asarray(v)[codes == p]
                     for k, v in block.items()}
                    for p in _range(P))

            rows = [_partition.options(num_returns=P).remote(u)
                    for u in upstream]
            return [_merge.remote(*[row[p] for row in rows])
                    for p in _range(P)]

        return Dataset([_RefSource(run_shuffle, name="HashShuffle")])

    def join(self, other: "Dataset", on: str, *, how: str = "inner",
             num_partitions: int = 8) -> "Dataset":
        """Distributed hash join (reference: _internal/execution/operators/
        join.py — hash-shuffle both sides by key, then per-partition joins).
        Both sides are partitioned with the same stable hash, so partition p
        of the left can only match partition p of the right; the P join
        tasks run cluster-side and the driver only routes refs — payload
        columns never materialize on the driver."""
        if how not in ("inner", "left", "right", "outer"):
            raise ValueError(f"unsupported join how={how!r}")
        left = self.hash_shuffle(on, num_partitions)
        right = other.hash_shuffle(on, num_partitions)

        def run_join() -> List[Any]:
            lrefs = list(_exec_stream(list(left._plan)))
            rrefs = list(_exec_stream(list(right._plan)))

            @ray_tpu.remote
            def _schema(b: Block):
                import numpy as np
                b = as_numpy_block(b)
                return [(c, str(np.asarray(v).dtype)) for c, v in b.items()]

            # Schema hints (column name + dtype — no payload): an empty
            # partition on one side must still produce the full merged
            # schema WITH matching key dtypes, or pd.merge raises on e.g.
            # int64-vs-object key columns and downstream block_concat sees
            # inconsistent blocks.
            def side_schema(refs, other_refs):
                for sch in ray_tpu.get([_schema.remote(r) for r in refs]):
                    if sch:
                        return sch
                # Whole side empty: payload columns are unknowable, but the
                # key column must still merge cleanly — borrow its dtype
                # from the other side.
                for sch in ray_tpu.get(
                        [_schema.remote(r) for r in other_refs]):
                    for c, dt in sch:
                        if c == on:
                            return [(on, dt)]
                return [(on, "int64")]

            lsch = side_schema(lrefs, rrefs)
            rsch = side_schema(rrefs, lrefs)

            @ray_tpu.remote
            def _join_part(lb: Block, rb: Block, on=on, how=how,
                           lsch=tuple(lsch), rsch=tuple(rsch)) -> Block:
                import numpy as np
                import pandas as pd

                def frame(b, sch):
                    b = as_numpy_block(b)
                    if b:
                        return pd.DataFrame(dict(b))
                    return pd.DataFrame(
                        {c: np.empty(0, dtype=np.dtype(dt))
                         for c, dt in sch})

                out = frame(lb, lsch).merge(frame(rb, rsch), on=on, how=how)
                return {c: out[c].to_numpy() for c in out.columns}

            return [_join_part.remote(l, r)
                    for l, r in zip(lrefs, rrefs)]

        return Dataset([_RefSource(run_join, name=f"Join({how})")])

    def zip(self, other: "Dataset") -> "Dataset":
        """Column-wise zip of two row-aligned datasets (reference:
        Dataset.zip). Right-side blocks are re-sliced to the left's block
        boundaries by cluster tasks; duplicate column names from the right
        get a "_1" suffix."""
        def run_zip() -> List[Any]:
            lrefs = list(_exec_stream(list(self._plan)))
            rrefs = list(_exec_stream(list(other._plan)))

            @ray_tpu.remote
            def _rows(b: Block) -> int:
                return block_num_rows(b)

            lcounts = ray_tpu.get([_rows.remote(r) for r in lrefs])
            rcounts = ray_tpu.get([_rows.remote(r) for r in rrefs])
            if sum(lcounts) != sum(rcounts):
                raise ValueError(
                    f"zip needs equal row counts; {sum(lcounts)} vs "
                    f"{sum(rcounts)}")

            # Right-block spans as (global_start, global_end, ref).
            spans = []
            pos = 0
            for ref, cnt in zip(rrefs, rcounts):
                spans.append((pos, pos + cnt, ref))
                pos += cnt

            @ray_tpu.remote
            def _zip_part(lb: Block, ranges, *rblocks) -> Block:
                lb = as_numpy_block(lb)
                parts = [block_slice(rb, lo, hi)
                         for rb, (lo, hi) in zip(rblocks, ranges)]
                nonempty = [p for p in parts if block_num_rows(p)]
                rb = as_numpy_block(
                    block_concat(nonempty) if nonempty else {})
                out = dict(lb)
                for k, v in rb.items():
                    out[k if k not in out else f"{k}_1"] = v
                return out

            out_refs = []
            pos = 0
            for lref, cnt in zip(lrefs, lcounts):
                lo, hi = pos, pos + cnt
                pos = hi
                needed = [(s, e, r) for s, e, r in spans
                          if e > lo and s < hi]
                ranges = [(max(lo, s) - s, min(hi, e) - s)
                          for s, e, _ in needed]
                out_refs.append(_zip_part.remote(
                    lref, ranges, *[r for _, _, r in needed]))
            return out_refs

        return Dataset([_RefSource(run_zip, name="Zip")])

    def split(self, n: int) -> List["Dataset"]:
        refs = list(self.iter_block_refs())
        out = []
        for i in _range(n):
            out.append(Dataset([_RefSource(refs[i::n])]))
        return out

    def union(self, *others: "Dataset") -> "Dataset":
        plans = [self._plan] + [o._plan for o in others]

        def gen(plans=plans):
            for p in plans:
                for ref in _exec_stream(p):
                    yield ray_tpu.get(ref)

        return Dataset([_Source(gen, name="Union")])

    # -- train integration ------------------------------------------------
    def streaming_split(self, n: int, *, equal: bool = False,
                        locality_hints=None) -> List["DataIterator"]:
        """N coordinated iterators for N train workers (reference:
        data/iterator.py streaming_split + SplitCoordinator actor)."""
        from ray_tpu.data.iterator import DataIterator, _SplitCoordinator

        Coord = ray_tpu.remote(_SplitCoordinator)
        coord = Coord.options(num_cpus=0.5).remote(self._plan, n)
        return [DataIterator(coordinator=coord, split_idx=i)
                for i in _range(n)]

    def iterator(self) -> "DataIterator":
        from ray_tpu.data.iterator import DataIterator

        return DataIterator(dataset=self)

    # -- write ------------------------------------------------------------
    def _write_parts(self, path: str, write_part: Callable) -> None:
        """Block-parallel write: one cluster task per block writes its own
        part file (reference: Data write ops run as tasks in the plan, not
        on the driver); the driver only routes refs and the final barrier
        returns row counts."""
        import os

        os.makedirs(path, exist_ok=True)

        @ray_tpu.remote
        def _w(block: Block, idx: int, path=path,
               write_part=write_part) -> int:
            write_part(block, idx, path)
            return block_num_rows(block)

        ray_tpu.get([_w.remote(ref, i)
                     for i, ref in enumerate(self.iter_block_refs())])

    def write_parquet(self, path: str) -> None:
        self._write_parts(path, _write_parquet_part)

    def write_json(self, path: str) -> None:
        """One JSONL file per block (reference: Dataset.write_json)."""
        self._write_parts(path, _write_json_part)

    def write_csv(self, path: str) -> None:
        self._write_parts(path, _write_csv_part)

    def to_pandas(self):
        """Materialize into one pandas DataFrame (driver memory)."""
        import pandas as pd

        blocks = list(self.iter_blocks())
        if not blocks:
            return pd.DataFrame()
        return pd.concat([as_pandas_batch(b) for b in blocks],
                         ignore_index=True)

    def stats(self) -> str:
        names = [getattr(op, "name", type(op).__name__) for op in self._plan]
        return " -> ".join(names)

    def __repr__(self) -> str:
        return f"Dataset(plan={self.stats()})"


def _write_parquet_part(block: Block, idx: int, path: str) -> None:
    import os

    import pyarrow.parquet as pq

    # Arrow blocks (e.g. straight from read_parquet/read_csv) write
    # directly — typed schemas (strings, nulls, nested lists) round-trip.
    table = as_arrow_block(block)
    pq.write_table(table, os.path.join(path, f"part-{idx:05d}.parquet"))


def _write_json_part(block: Block, idx: int, path: str) -> None:
    import json
    import os

    block = as_numpy_block(block)

    with open(os.path.join(path, f"part-{idx:05d}.jsonl"), "w") as f:
        for row in block_to_items(block):
            if not isinstance(row, dict):
                row = {VALUE_COL: row}
            f.write(json.dumps(
                {k: (v.tolist() if isinstance(v, np.ndarray)
                     else v.item() if isinstance(v, np.generic)
                     else v) for k, v in row.items()}) + "\n")


def _write_csv_part(block: Block, idx: int, path: str) -> None:
    import csv
    import os

    block = as_numpy_block(block)

    cols = list(block.keys())
    with open(os.path.join(path, f"part-{idx:05d}.csv"), "w",
              newline="") as f:
        w = csv.writer(f)
        w.writerow(cols)
        for j in _range(block_num_rows(block)):
            w.writerow([block[c][j] for c in cols])


def _stable_hash_codes(vals, P: int) -> np.ndarray:
    """Partition codes that are identical in EVERY worker process —
    builtin hash() is per-process seed-randomized and would scatter one
    key across partitions."""
    import zlib

    arr = np.asarray(vals)
    if arr.dtype.kind in "iub":
        return (arr.astype(np.int64) % P).astype(np.int64)
    return np.array(
        [zlib.crc32(repr(x).encode()) % P for x in arr], np.int64)


class GroupedData:
    """Groupby aggregations (reference: data/grouped_data.py). Driver-side
    composition by default; with pre_partitioned=True (hash_shuffle ran
    first, so every block holds complete groups) the aggregation itself is
    a per-block cluster task."""

    def __init__(self, ds: Dataset, key: str, pre_partitioned: bool = False):
        self._ds = ds
        self._key = key
        self._pre_partitioned = pre_partitioned

    def _gather(self):
        full = block_concat(list(self._ds.iter_blocks()))
        keys = np.asarray(full[self._key])
        uniq, inv = np.unique(keys, return_inverse=True)
        return full, uniq, inv

    def _agg(self, fn, cols: Optional[Sequence[str]], suffix: str) -> Dataset:
        if self._pre_partitioned:
            # Complete groups per block → aggregation is a per-block TASK.
            key = self._key

            def agg_block(block, key=key, fn=fn, cols=cols, suffix=suffix):
                if not block_num_rows(block):
                    return {}
                block = as_numpy_block(block)
                keys = np.asarray(block[key])
                uniq, inv = np.unique(keys, return_inverse=True)
                use = [c for c in (cols or block.keys()) if c != key]
                out = {key: uniq}
                for c in use:
                    vals = np.asarray(block[c])
                    # NB: _range — this module shadows builtin range with
                    # the Dataset factory.
                    out[f"{c}_{suffix}"] = np.asarray(
                        [fn(vals[inv == g]) for g in _range(len(uniq))])
                return out

            return Dataset(self._ds._plan + [_MapBatches(
                agg_block, batch_size=None, name=f"GroupAgg({suffix})")])
        full, uniq, inv = self._gather()
        cols = [c for c in (cols or full.keys()) if c != self._key]
        out: Dict[str, np.ndarray] = {self._key: uniq}
        for c in cols:
            vals = np.asarray(full[c])
            out[f"{c}_{suffix}"] = np.asarray(
                [fn(vals[inv == g]) for g in _range(len(uniq))])
        return from_items(block_to_items(out))

    def count(self) -> Dataset:
        if self._pre_partitioned:
            key = self._key

            def count_block(block, key=key):
                if not block_num_rows(block):
                    return {}
                block = as_numpy_block(block)
                keys = np.asarray(block[key])
                uniq, inv = np.unique(keys, return_inverse=True)
                return {key: uniq,
                        "count": np.bincount(inv, minlength=len(uniq))}

            return Dataset(self._ds._plan + [_MapBatches(
                count_block, batch_size=None, name="GroupCount")])
        full, uniq, inv = self._gather()
        counts = np.bincount(inv, minlength=len(uniq))
        return from_items(block_to_items(
            {self._key: uniq, "count": counts}))

    def sum(self, cols: Optional[Sequence[str]] = None) -> Dataset:
        return self._agg(np.sum, cols, "sum")

    def mean(self, cols: Optional[Sequence[str]] = None) -> Dataset:
        return self._agg(np.mean, cols, "mean")

    def min(self, cols: Optional[Sequence[str]] = None) -> Dataset:
        return self._agg(np.min, cols, "min")

    def max(self, cols: Optional[Sequence[str]] = None) -> Dataset:
        return self._agg(np.max, cols, "max")

    def map_groups(self, fn: Callable) -> Dataset:
        full, uniq, inv = self._gather()
        items: List[Any] = []
        for g in _range(len(uniq)):
            group = {k: v[inv == g] for k, v in full.items()}
            res = fn(group)
            if isinstance(res, list):
                items.extend(res)
            else:
                items.append(res)
        return from_items(items)


def _remote_num_rows():
    @ray_tpu.remote
    def _n(block: Block) -> int:
        return block_num_rows(block)

    return _n


# ---------------------------------------------------------------------------
# Read API (reference: python/ray/data/read_api.py)
# ---------------------------------------------------------------------------
def from_items(items: Sequence[Any], *,
               block_rows: int = DEFAULT_BLOCK_ROWS) -> Dataset:
    items = list(items)

    def gen():
        for i in _range(0, len(items), block_rows):
            yield block_from_items(items[i:i + block_rows])

    return Dataset([_Source(gen, name="FromItems")])


def range(n: int, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> Dataset:  # noqa: A001
    def gen():
        for i in _range(0, n, block_rows):
            yield {"id": np.arange(i, min(i + block_rows, n))}

    return Dataset([_Source(gen, name="Range")])


def range_tensor(n: int, *, shape=(1,),
                 block_rows: int = DEFAULT_BLOCK_ROWS) -> Dataset:
    def gen():
        for i in _range(0, n, block_rows):
            ids = np.arange(i, min(i + block_rows, n))
            data = np.broadcast_to(
                ids.reshape((-1,) + (1,) * len(shape)),
                (len(ids),) + tuple(shape)).copy()
            yield {"data": data}

    return Dataset([_Source(gen, name="RangeTensor")])


def from_numpy(arr: np.ndarray, *, column: str = "data",
               block_rows: int = DEFAULT_BLOCK_ROWS) -> Dataset:
    def gen():
        for i in _range(0, len(arr), block_rows):
            yield {column: arr[i:i + block_rows]}

    return Dataset([_Source(gen, name="FromNumpy")])


def from_pandas(df) -> Dataset:
    def gen():
        yield {c: df[c].to_numpy() for c in df.columns}

    return Dataset([_Source(gen, name="FromPandas")])


def read_parquet(path: str) -> Dataset:
    """One block per parquet file (reference: read_api.py read_parquet)."""
    paths = _expand_paths(path, ".parquet")

    def gen():
        import pyarrow.parquet as pq

        for p in paths:
            # Arrow-native block: typed schema (strings, nulls, nested
            # lists) survives; numeric columns convert zero-copy at the
            # compute boundary (reference: _internal/arrow_block.py:194).
            yield pq.read_table(p)

    return Dataset([_Source(gen, name="ReadParquet")])


def read_csv(path: str) -> Dataset:
    """One Arrow block per csv file — columns come back TYPED (ints/floats
    inferred), not as strings (reference: read_api.py read_csv via
    pyarrow.csv)."""
    paths = _expand_paths(path, ".csv")

    def gen():
        from pyarrow import csv as pa_csv

        for p in paths:
            table = pa_csv.read_csv(p)
            if table.num_rows:
                yield table

    return Dataset([_Source(gen, name="ReadCSV")])


def _expand_paths(path: str, suffix: str) -> List[str]:
    import glob
    import os

    if os.path.isdir(path):
        # glob already returns dir-prefixed paths — no second join.
        return sorted(glob.glob(os.path.join(path, f"*{suffix}")))
    return sorted(glob.glob(path)) or [path]


def read_json(path: str, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> Dataset:
    """JSONL file(s) → dataset, one or more blocks per file (reference:
    read_api.py read_json)."""
    paths = _expand_paths(path, ".jsonl")

    def gen():
        import json

        for p in paths:
            rows = []
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    rows.append(json.loads(line))
                    if len(rows) >= block_rows:
                        yield block_from_items(rows)
                        rows = []
            if rows:
                yield block_from_items(rows)

    return Dataset([_Source(gen, name="ReadJSON")])


def read_text(path: str, *, block_rows: int = DEFAULT_BLOCK_ROWS) -> Dataset:
    """Text file(s) → one row per line, column "text" (reference:
    read_api.py read_text)."""
    paths = _expand_paths(path, ".txt")

    def gen():
        for p in paths:
            lines = []
            with open(p) as f:
                for line in f:
                    lines.append({"text": line.rstrip("\n")})
                    if len(lines) >= block_rows:
                        yield block_from_items(lines)
                        lines = []
            if lines:
                yield block_from_items(lines)

    return Dataset([_Source(gen, name="ReadText")])
