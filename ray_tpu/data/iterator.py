"""DataIterator + split coordination for Train ingest.

Reference: python/ray/data/iterator.py (`DataIterator.iter_batches`) and the
streaming_split SplitCoordinator actor
(_internal/execution/operators/output_splitter.py). Redesign: the coordinator
is a plain actor running the streaming executor; consumers pull block refs
round-robin with per-split buffering — pulling is the backpressure.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional

from ray_tpu.data.block import (
    Block,
    block_concat,
    block_num_rows,
    block_slice,
)


class _SplitCoordinator:
    """Actor: executes the plan once per epoch, dealing blocks to n splits
    round-robin.

    A StreamingExecutor terminated in an OutputSplitter — the pump-on-pull
    loop runs inside this actor process (no background thread), and the
    splitter deals eagerly into per-split queues so one far-behind consumer
    never stalls the others (the dealt blocks leave the execution's byte
    budget; see OutputSplitter)."""

    def __init__(self, plan: List[Any], n: int):
        self._plan = plan
        self._n = n
        self._epoch = 0
        self._exec = None  # this epoch's StreamingExecutor

    def _ensure_stream(self):
        if self._exec is None:
            from ray_tpu.data._execution import StreamingExecutor

            self._exec = StreamingExecutor(self._plan, split_n=self._n)

    def next_block(self, split_idx: int) -> Optional[Block]:
        """Returns the next block for split i (as a value — task-result
        ownership transfers it to the caller; handing out raw refs would race
        the coordinator's ref-count drop against the consumer's borrow)."""
        import ray_tpu

        self._ensure_stream()
        try:
            ref = self._exec.next_for_split(split_idx)
        except StopIteration:
            return None
        return ray_tpu.get(ref)

    def reset(self):
        """Start a fresh epoch (re-runs the plan). Blocks already dealt to
        a split but not yet pulled belong to the finished epoch and are
        discarded — epoch boundaries are the trainer's barrier."""
        if self._exec is not None:
            self._exec.shutdown()
            self._exec = None
        self._epoch += 1

    def epoch(self) -> int:
        return self._epoch

    def stats(self) -> Optional[Dict[str, Any]]:
        """Live executor summary (per-op telemetry breakdown), None before
        the first pull of an epoch."""
        if self._exec is None:
            return None
        return self._exec.summary()


class DataIterator:
    """Per-consumer iterator; picklable (ships an actor handle or a plan).

    Reference: data/iterator.py — `get_dataset_shard` returns one of these
    inside each train worker."""

    def __init__(self, *, dataset: Any = None, coordinator: Any = None,
                 split_idx: int = 0):
        self._dataset = dataset
        self._coordinator = coordinator
        self._split_idx = split_idx

    def _block_iter(self) -> Iterator[Block]:
        import ray_tpu

        if self._coordinator is not None:
            while True:
                block = ray_tpu.get(
                    self._coordinator.next_block.remote(self._split_idx))
                if block is None:
                    return
                yield block
        else:
            yield from self._dataset.iter_blocks()

    def iter_batches(self, *, batch_size: Optional[int] = 256,
                     prefetch_batches: int = 1,
                     drop_last: bool = False) -> Iterator[Block]:
        leftover: Optional[Block] = None
        for block in self._block_iter():
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
        if (leftover is not None and block_num_rows(leftover)
                and not drop_last):
            yield leftover

    def iter_rows(self) -> Iterator[Any]:
        from ray_tpu.data.block import block_to_items

        for block in self._block_iter():
            yield from block_to_items(block)

    def materialize_all(self) -> List[Block]:
        return list(self._block_iter())

    def new_epoch(self) -> None:
        if self._coordinator is not None and self._split_idx == 0:
            import ray_tpu

            ray_tpu.get(self._coordinator.reset.remote())
