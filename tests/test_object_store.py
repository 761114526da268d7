"""Native shm store unit tests (reference test analog:
src/ray/object_manager/plasma tests + test_object_store.py)."""

import os

import numpy as np
import pytest

from ray_tpu._private import serialization as ser
from ray_tpu._private.ids import JobID, ObjectID, TaskID
from ray_tpu.core.object_store import SharedMemoryStore
from ray_tpu.exceptions import ObjectStoreFullError


@pytest.fixture
def store(tmp_path):
    path = f"/dev/shm/ray_tpu_test_{os.getpid()}_{os.urandom(4).hex()}"
    s = SharedMemoryStore(path, capacity=32 * 1024 * 1024, create=True)
    yield s
    s.close(unmap=True)
    os.unlink(path)


_TID = TaskID(b"\x01" * 12 + JobID.from_int(1).binary())


def _oid(i=0):
    # Deterministic: TaskID.for_task is random per call, so ids must be derived
    # from a fixed task for lookups made with freshly-built ObjectIDs to match.
    return ObjectID.for_put(_TID, i)


def test_put_get_raw(store):
    oid = _oid()
    assert store.put_raw(oid, [b"hello", b"world"])
    view = store.get_raw(oid)
    assert bytes(view) == b"helloworld"
    store.release(oid)


def test_put_duplicate_returns_false(store):
    oid = _oid()
    assert store.put_raw(oid, [b"x"])
    assert not store.put_raw(oid, [b"y"])


def test_serialized_roundtrip(store):
    oid = _oid()
    arr = np.arange(10000, dtype=np.int64)
    store.put_serialized(oid, ser.serialize({"a": arr}))
    out = ser.deserialize(store.get_serialized(oid))
    np.testing.assert_array_equal(out["a"], arr)
    # The read pin is held by the deserialized array's buffer chain and
    # auto-releases on GC — no explicit release.


def test_missing_object(store):
    assert store.get_raw(_oid(123)) is None
    assert not store.contains(_oid(123))


def test_lru_eviction_under_pressure(store):
    # 32MB store, write 40 x 1MB: early unpinned objects must be evicted.
    for i in range(40):
        store.put_raw(_oid(i), [b"z" * (1024 * 1024)])
    assert store.contains(_oid(39))
    assert not store.contains(_oid(0))


def test_oversized_object_raises(store):
    with pytest.raises(ObjectStoreFullError):
        store.put_raw(_oid(7), [b"x" * (64 * 1024 * 1024)])


def test_pinned_objects_survive_pressure(store):
    pinned = _oid(999)
    store.put_raw(pinned, [b"p" * 1024])
    view = store.get_raw(pinned)  # pin it
    for i in range(40):
        store.put_raw(_oid(i), [b"z" * (1024 * 1024)])
    assert store.contains(pinned)
    assert bytes(view[:1]) == b"p"
    store.release(pinned)


def test_delete(store):
    oid = _oid(5)
    store.put_raw(oid, [b"bye"])
    store.delete(oid)
    assert not store.contains(oid)


def test_cross_handle_visibility(store):
    other = SharedMemoryStore(store.path)
    oid = _oid(77)
    store.put_raw(oid, [b"shared"])
    view = other.get_raw(oid)
    assert bytes(view) == b"shared"
    other.release(oid)
    other.close()


def test_read_pin_autoreleases_on_gc(store):
    """get_serialized pins; dropping every deserialized consumer must unpin
    so the object becomes evictable (the round-1 pin leak)."""
    import gc

    oid = _oid(500)
    arr = np.arange(50000, dtype=np.int64)
    store.put_serialized(oid, ser.serialize(arr))
    out = ser.deserialize(store.get_serialized(oid))
    np.testing.assert_array_equal(out, arr)
    del out
    gc.collect()
    # Pin released → eviction under pressure can reclaim it.
    for i in range(40):
        store.put_raw(_oid(1000 + i), [b"z" * (1024 * 1024)])
    assert not store.contains(oid)


def test_read_pin_protects_live_array(store):
    """While a zero-copy deserialized array is alive the object must stay
    pinned (not evicted/corrupted) under memory pressure."""
    oid = _oid(600)
    arr = np.arange(50000, dtype=np.int64)
    store.put_serialized(oid, ser.serialize(arr))
    out = ser.deserialize(store.get_serialized(oid))
    for i in range(40):
        store.put_raw(_oid(2000 + i), [b"z" * (1024 * 1024)])
    assert store.contains(oid)
    np.testing.assert_array_equal(out, arr)


def test_overflow_spilling_roundtrip(tmp_path):
    """Objects that exceed the arena spill to disk and read back
    transparently (reference: local_object_manager.h spilling)."""
    import numpy as np

    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    try:
        # 5 x 30MB > 64MB arena: later puts must spill, all must resolve.
        arrays = [np.full(30 * 1024 * 1024, i, dtype=np.uint8)
                  for i in range(5)]
        refs = [ray_tpu.put(a) for a in arrays]
        for i, r in enumerate(refs):
            out = ray_tpu.get(r, timeout=60)
            assert out[0] == i and len(out) == 30 * 1024 * 1024
        # Task results overflow too.
        @ray_tpu.remote
        def big(i):
            import numpy as np

            return np.full(30 * 1024 * 1024, 100 + i, dtype=np.uint8)

        refs2 = [big.remote(i) for i in range(3)]
        for i, r in enumerate(refs2):
            assert ray_tpu.get(r, timeout=120)[0] == 100 + i
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("which", ["memory_store", "task_manager"])
def test_ref_zero_inside_a_critical_section_does_not_wait_for_itself(which):
    """An id's `__hash__` is Python: the cyclic GC can run under the memory
    store's or the task manager's lock and free an ObjectRef, whose
    ref-zero path (`_on_owned_ref_zero`: `MemoryStore.pop`, then
    `TaskManager.drop_lineage`) takes the same lock on the same thread.
    Here the hash itself plays the collector. With plain locks the IO
    loop's thread waited for itself in `put` and every `get` of the process
    hung (test_serve_overload.py under load, PR 40)."""
    import asyncio
    import threading

    from ray_tpu._private.task_manager import TaskManager
    from ray_tpu.core.object_store import MemoryStore

    store = MemoryStore(asyncio.new_event_loop())
    tasks = TaskManager(lambda oid, result: None)
    store.put(_oid(1), "freed by the collector")

    class Collecting(ObjectID):
        def __hash__(self):
            store.pop(_oid(1), None)
            tasks.drop_lineage(_oid(1))
            return super().__hash__()

    oid = Collecting(_oid(2).binary())
    call = {"memory_store": lambda: store.put(oid, "value"),
            "task_manager": lambda: tasks.lineage_spec(oid)}[which]
    t = threading.Thread(target=call, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), f"{which}: the thread waits for its own lock"
    assert store.size() == (1 if which == "memory_store" else 0)
