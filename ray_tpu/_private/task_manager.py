"""Owner-side task tracking: pending tasks, retries, result completion.

Counterpart of src/ray/core_worker/task_manager.h:168 (TaskManager): the owner
of a task's return refs keeps the spec for retry (lineage), marks returns
available on completion, and decides retry-vs-fail on worker errors.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.serialization import SerializedObject
from ray_tpu._private.task_spec import TaskSpec, TaskType


class PendingTask:
    __slots__ = ("spec", "retries_left", "inflight_on")

    def __init__(self, spec: TaskSpec, retries_left: int):
        self.spec = spec
        self.retries_left = retries_left
        self.inflight_on: Optional[Tuple[str, int]] = None


class TaskManager:
    def __init__(self, put_result: Callable[[ObjectID, Any], None]):
        self._pending: Dict[TaskID, PendingTask] = {}
        self._lineage: Dict[ObjectID, TaskSpec] = {}
        # Reentrant, as MemoryStore's: a GC pass inside a critical section
        # can free an ObjectRef, whose ref-zero path ends in `drop_lineage`
        # on the same thread.
        self._lock = threading.RLock()
        self._put_result = put_result

    def add_pending(self, spec: TaskSpec) -> List[ObjectID]:
        with self._lock:
            self._pending[spec.task_id] = PendingTask(spec, spec.max_retries)
        return spec.return_ids()

    def num_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def mark_inflight(self, task_id: TaskID, addr: Tuple[str, int]) -> None:
        with self._lock:
            pt = self._pending.get(task_id)
            if pt:
                pt.inflight_on = addr

    def complete(self, task_id: TaskID, results: List[Any]) -> None:
        """results[i] is whatever the executor replied per return value —
        stored via the put_result callback (worker decides inline vs shm)."""
        with self._lock:
            pt = self._pending.pop(task_id, None)
        if pt is None:
            return
        for i, result in enumerate(results):
            oid = ObjectID.for_task_return(task_id, i)
            # Lineage retention (reference: TaskManager lineage pinning +
            # object_recovery_manager.h:43): keep the spec of normal tasks
            # whose outputs may need re-execution after object loss. Actor
            # results are excluded (re-running a method against mutated
            # actor state is not replay-safe).
            if pt.spec.task_type == TaskType.NORMAL_TASK:
                with self._lock:
                    self._lineage[oid] = pt.spec
            self._put_result(oid, result)

    def lineage_spec(self, object_id: ObjectID) -> Optional[TaskSpec]:
        with self._lock:
            return self._lineage.get(object_id)

    def drop_lineage(self, object_id: ObjectID) -> None:
        with self._lock:
            spec = self._lineage.pop(object_id, None)
        # The spec's destruction can cascade (its ObjectRef args drop their
        # local refs -> _on_owned_ref_zero -> drop_lineage again). Outside
        # the lock, so that other threads do not wait for the whole cascade
        # (the lock is reentrant: inside it the cascade would not deadlock).
        del spec

    def fail_or_retry(self, task_id: TaskID) -> Optional[TaskSpec]:
        """On a retryable failure: return the spec to resubmit, or None if
        retries are exhausted (caller then stores the error)."""
        with self._lock:
            pt = self._pending.get(task_id)
            if pt is None:
                return None
            if pt.retries_left > 0:
                pt.retries_left -= 1
                pt.inflight_on = None
                return pt.spec
            return None

    def fail_permanently(self, task_id: TaskID, error: SerializedObject) -> None:
        with self._lock:
            pt = self._pending.pop(task_id, None)
        if pt is None:
            return
        for oid in pt.spec.return_ids():
            self._put_result(oid, error)

    def get_spec(self, task_id: TaskID) -> Optional[TaskSpec]:
        with self._lock:
            pt = self._pending.get(task_id)
            return pt.spec if pt else None
