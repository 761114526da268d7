"""Object plane: per-node shared-memory store + per-worker memory store.

Counterparts in the reference:
- ``SharedMemoryStore`` ≙ plasma client (src/ray/object_manager/plasma/client.h:241)
  over the native arena in ray_tpu/native/shm_store.cc.
- ``MemoryStore`` ≙ the core worker's in-memory store for small/inlined objects
  (src/ray/core_worker/store_provider/memory_store/memory_store.h:45) — holds
  SerializedObjects and wakes blocked getters via asyncio events.

Serialized values are stored as: [u32 metadata_len][metadata][u32 nbufs]
([u64 buf_len][buf])* so multi-buffer zero-copy objects round-trip without an
extra concatenation copy on write.
"""

from __future__ import annotations

import asyncio
import ctypes
import os
import struct
import threading
import time
from typing import Dict, List, Optional

from ray_tpu._private.ids import ObjectID
from ray_tpu._private.serialization import SerializedObject
from ray_tpu.exceptions import ObjectStoreFullError
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)

SHM_OK = 0
SHM_ERR_EXISTS = -1
SHM_ERR_NOT_FOUND = -2
SHM_ERR_FULL = -3


def _arena_puts_counter():
    """Arena put outcomes — hit rate = hit / (hit + full). Lazy import:
    the metrics registry must not join this module's import chain (worker
    imports the store before the util package finishes initializing)."""
    from ray_tpu.util import metrics as um

    return um.get_counter(
        "ray_tpu_object_store_arena_puts_total",
        "Shared-memory arena put attempts by outcome (hit|full)",
        tag_keys=("result",))


def _spilled_objects_counter():
    from ray_tpu.util import metrics as um

    return um.get_counter("ray_tpu_object_store_spilled_objects_total",
                          "Objects spilled from the arena to disk")


def _spilled_bytes_counter():
    from ray_tpu.util import metrics as um

    return um.get_counter("ray_tpu_object_store_spilled_bytes_total",
                          "Bytes spilled from the arena to disk")


_PHASE_BOUNDARIES = (0.00001, 0.0001, 0.0005, 0.001, 0.005, 0.01,
                     0.05, 0.1, 0.5, 1.0)


def _put_phase_histogram():
    """Flight-recorder phase decomposition for large puts: alloc (arena
    reservation) / memcpy / seal — the profile the red
    `single_client_put_gigabytes` row needs."""
    from ray_tpu.util import metrics as um

    return um.get_histogram(
        "ray_tpu_object_store_put_phase_seconds",
        "Shared-memory put phases (alloc|memcpy|seal)",
        boundaries=_PHASE_BOUNDARIES, tag_keys=("phase",))


def _get_phase_histogram():
    """Per-ref get decomposition: lookup (index probe) / anchor (numpy
    view + release finalizer) / parse (header+buffer walk) — the per-ref
    cost profile behind `get_object_containing_10k_refs`."""
    from ray_tpu.util import metrics as um

    return um.get_histogram(
        "ray_tpu_object_store_get_phase_seconds",
        "Shared-memory get phases (lookup|anchor|parse)",
        boundaries=_PHASE_BOUNDARIES, tag_keys=("phase",))


def _load_native():
    from ray_tpu.native import build_library

    lib = ctypes.CDLL(build_library("shm_store"))
    lib.shm_store_create.restype = ctypes.c_void_p
    lib.shm_store_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.shm_store_open.restype = ctypes.c_void_p
    lib.shm_store_open.argtypes = [ctypes.c_char_p]
    lib.shm_store_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.shm_store_abort.restype = ctypes.c_int
    lib.shm_store_abort.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.shm_store_reclaim_stale.restype = ctypes.c_int
    lib.shm_store_reclaim_stale.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.shm_store_create_object.restype = ctypes.c_int
    lib.shm_store_create_object.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.shm_store_seal.restype = ctypes.c_int
    lib.shm_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.shm_store_get.restype = ctypes.c_int
    lib.shm_store_get.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.shm_store_contains.restype = ctypes.c_int
    lib.shm_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.shm_store_release.restype = ctypes.c_int
    lib.shm_store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.shm_store_delete.restype = ctypes.c_int
    lib.shm_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.shm_store_base.restype = ctypes.c_void_p
    lib.shm_store_base.argtypes = [ctypes.c_void_p]
    lib.shm_store_map_size.restype = ctypes.c_uint64
    lib.shm_store_map_size.argtypes = [ctypes.c_void_p]
    lib.shm_store_bytes_in_use.restype = ctypes.c_uint64
    lib.shm_store_bytes_in_use.argtypes = [ctypes.c_void_p]
    lib.shm_store_capacity.restype = ctypes.c_uint64
    lib.shm_store_capacity.argtypes = [ctypes.c_void_p]
    lib.shm_store_num_objects.restype = ctypes.c_uint64
    lib.shm_store_num_objects.argtypes = [ctypes.c_void_p]
    lib.shm_store_prefault.restype = None
    lib.shm_store_prefault.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.shm_store_prefault_done.restype = ctypes.c_int
    lib.shm_store_prefault_done.argtypes = [ctypes.c_void_p]
    lib.shm_store_set_auto_evict.restype = None
    lib.shm_store_set_auto_evict.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.shm_store_lru_candidate.restype = ctypes.c_int
    lib.shm_store_lru_candidate.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.shm_store_write.restype = None
    lib.shm_store_write.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_int,
    ]
    return lib


_native_lib = None
_native_lock = threading.Lock()


def native_lib():
    global _native_lib
    with _native_lock:
        if _native_lib is None:
            _native_lib = _load_native()
    return _native_lib


class SharedMemoryStore:
    """ctypes client of the native arena. Thread-safe (the native side locks)."""

    def __init__(self, path: str, capacity: Optional[int] = None,
                 create: bool = False, prefault: bool = True):
        self.path = path
        self._lib = native_lib()
        if create:
            assert capacity is not None
            self._handle = self._lib.shm_store_create(path.encode(), capacity)
        else:
            self._handle = self._lib.shm_store_open(path.encode())
        if not self._handle:
            raise OSError(f"failed to {'create' if create else 'open'} shm store {path}")
        # Background page prefault. The creator's MADV_POPULATE_WRITE
        # allocates the tmpfs pages once; other long-lived processes
        # (drivers) sweep too so their large puts hit populated PTEs. But
        # WORKERS skip it: a short-lived worker never amortizes a
        # full-arena PTE sweep (~0.3 s of one-core work per 2 GiB —
        # measured 8x slower 50-actor churn windows with per-worker
        # sweeps) and faults in lazily instead. Peer-arena READERS
        # (same-host cross-nodelet pulls) pass prefault=False: the pages
        # they touch are already resident in the owner's mapping.
        if prefault and (create
                         or not os.environ.get("RAY_TPU_WORKER_ID")):
            self._lib.shm_store_prefault(self._handle, 1 if create else 0)
        else:
            self._prefault_skipped = True
        base = self._lib.shm_store_base(self._handle)
        size = self._lib.shm_store_map_size(self._handle)
        self._base_addr = base
        self._view = (ctypes.c_char * size).from_address(base)
        self._mem = memoryview(self._view).cast("B")

    # -- raw bytes API --

    def put_raw(self, object_id: ObjectID, payload_parts: List[bytes]) -> bool:
        """Write an object as concatenated parts. False if it already exists.

        Flight-recorder phase stamps (alloc/memcpy/seal) are always-on for
        puts ≥1 MiB (3 perf_counter calls are noise against a memcpy that
        size) and sampled 1-in-N below it."""
        from ray_tpu._private import flight_recorder as _fr

        total = sum(len(p) for p in payload_parts)
        timed = _fr.enabled() and (total >= 1 << 20
                                   or _fr.maybe_sample())
        t0 = time.perf_counter() if timed else 0.0
        off = ctypes.c_uint64()
        rc = self._lib.shm_store_create_object(
            self._handle, object_id.binary(), total, ctypes.byref(off)
        )
        if rc == SHM_ERR_EXISTS:
            return False
        if rc == SHM_ERR_FULL:
            _arena_puts_counter().inc(tags={"result": "full"})
            raise ObjectStoreFullError(
                f"object of {total} bytes does not fit in store {self.path}"
            )
        if rc != SHM_OK:
            raise OSError(f"shm create failed rc={rc}")
        _arena_puts_counter().inc(tags={"result": "hit"})
        t1 = time.perf_counter() if timed else 0.0
        try:
            pos = off.value
            for part in payload_parts:
                n = len(part)
                if n >= 8 * 1024 * 1024:
                    # Parallel native copy for big buffers (memcpy is
                    # memory-bandwidth bound; one thread saturates ~5 GiB/s).
                    # numpy yields a pointer for readonly buffers too.
                    import numpy as _np

                    src_arr = _np.frombuffer(part, dtype=_np.uint8)
                    nthreads = min(8, os.cpu_count() or 1)
                    self._lib.shm_store_write(
                        self._handle, pos, src_arr.ctypes.data, n, nthreads)
                else:
                    src = bytes(part) if isinstance(part, memoryview) else part
                    ctypes.memmove(self._base_addr + pos, src, n)
                pos += n
        except BaseException:
            self._lib.shm_store_abort(self._handle, object_id.binary())
            raise
        t2 = time.perf_counter() if timed else 0.0
        self._lib.shm_store_seal(self._handle, object_id.binary())
        self._lib.shm_store_release(self._handle, object_id.binary())
        if timed:
            t3 = time.perf_counter()
            h = _put_phase_histogram()
            h.observe(t1 - t0, tags={"phase": "alloc"})
            h.observe(t2 - t1, tags={"phase": "memcpy"})
            h.observe(t3 - t2, tags={"phase": "seal"})
            if total >= 8 * 1024 * 1024:
                _fr.record_event(
                    "store_put", nbytes=total,
                    total_us=round((t3 - t0) * 1e6, 1),
                    alloc_us=round((t1 - t0) * 1e6, 1),
                    memcpy_us=round((t2 - t1) * 1e6, 1),
                    seal_us=round((t3 - t2) * 1e6, 1),
                    gib_per_s=round(
                        total / max(t2 - t1, 1e-9) / (1 << 30), 2))
        return True

    def get_raw(self, object_id: ObjectID) -> Optional[memoryview]:
        """Zero-copy view of a sealed object, or None. Caller must release()."""
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        rc = self._lib.shm_store_get(
            self._handle, object_id.binary(), ctypes.byref(off), ctypes.byref(size)
        )
        if rc != SHM_OK:
            return None
        return self._mem[off.value : off.value + size.value]

    def release(self, object_id: ObjectID) -> None:
        if not self._handle:  # store closed; pin dies with the mapping
            return
        self._lib.shm_store_release(self._handle, object_id.binary())

    def contains(self, object_id: ObjectID) -> bool:
        return bool(self._lib.shm_store_contains(self._handle, object_id.binary()))

    def set_auto_evict(self, enabled: bool) -> None:
        self._lib.shm_store_set_auto_evict(self._handle, 1 if enabled else 0)

    def lru_candidate(self) -> Optional[ObjectID]:
        buf = ctypes.create_string_buffer(20)
        rc = self._lib.shm_store_lru_candidate(self._handle, buf)
        if rc != SHM_OK:
            return None
        return ObjectID(buf.raw)

    def delete(self, object_id: ObjectID) -> None:
        self._lib.shm_store_delete(self._handle, object_id.binary())

    # -- SerializedObject API --

    def put_serialized(self, object_id: ObjectID, obj: SerializedObject) -> bool:
        parts = [struct.pack(">I", len(obj.metadata)), obj.metadata,
                 struct.pack(">I", len(obj.buffers))]
        for buf in obj.buffers:
            parts.append(struct.pack(">Q", len(buf)))
            parts.append(buf)
        return self.put_raw(object_id, parts)

    def get_serialized(self, object_id: ObjectID) -> Optional[SerializedObject]:
        """Reconstruct a SerializedObject. Buffers are zero-copy memoryviews
        into the arena. The read pin is tied to the buffers' lifetime: when
        the last consumer (including numpy arrays deserialized zero-copy on
        top of them) is garbage-collected, the pin is released and the object
        becomes evictable — the plasma client's Buffer-release semantics
        (reference: plasma/client.h Release on buffer destruction)."""
        from ray_tpu._private import flight_recorder as _fr

        # Sampled phase stamps only: ref-heavy gets run this per ref
        # (10k-ref benches), so even cheap stamps must not be per-op.
        timed = _fr.enabled() and _fr.maybe_sample()
        t0 = time.perf_counter() if timed else 0.0
        view = self.get_raw(object_id)
        if view is None:
            return None
        t1 = time.perf_counter() if timed else 0.0
        import weakref

        import numpy as np

        # All handed-out buffers are views of `anchor`; its finalizer fires
        # once every consumer has dropped its reference.
        anchor = np.frombuffer(view, dtype=np.uint8)
        weakref.finalize(anchor, self.release, object_id)
        avm = memoryview(anchor)
        t2 = time.perf_counter() if timed else 0.0
        (mlen,) = struct.unpack(">I", view[:4])
        metadata = bytes(view[4 : 4 + mlen])
        pos = 4 + mlen
        (nbufs,) = struct.unpack(">I", view[pos : pos + 4])
        pos += 4
        buffers: List[memoryview] = []
        for _ in range(nbufs):
            (blen,) = struct.unpack(">Q", view[pos : pos + 8])
            pos += 8
            buffers.append(avm[pos : pos + blen])
            pos += blen
        if timed:
            h = _get_phase_histogram()
            h.observe(t1 - t0, tags={"phase": "lookup"})
            h.observe(t2 - t1, tags={"phase": "anchor"})
            h.observe(time.perf_counter() - t2, tags={"phase": "parse"})
        return SerializedObject(metadata, buffers, [])  # type: ignore[arg-type]

    def stats(self) -> Dict[str, int]:
        return {
            "capacity": self._lib.shm_store_capacity(self._handle),
            "bytes_in_use": self._lib.shm_store_bytes_in_use(self._handle),
            "num_objects": self._lib.shm_store_num_objects(self._handle),
        }

    def wait_prefault(self, timeout_s: float = 60.0) -> bool:
        """Block until the background page-population pass completes (used by
        benchmarks; ordinary operation never needs to wait). Clients skip
        the sweep entirely (see __init__) — nothing to wait for."""
        import time as _time

        if getattr(self, "_prefault_skipped", False):
            return True
        deadline = _time.monotonic() + timeout_s
        while _time.monotonic() < deadline:
            if self._lib.shm_store_prefault_done(self._handle):
                return True
            _time.sleep(0.05)
        return False

    def reclaim_stale(self, age_s: int = 60) -> int:
        """Reclaim orphaned in-progress creates from dead writers."""
        return self._lib.shm_store_reclaim_stale(self._handle, age_s)

    def close(self, unmap: bool = False) -> None:
        """Close the handle. By default the mapping stays alive until process
        exit because zero-copy views from get_raw may still be referenced;
        pass unmap=True only when no views can be outstanding."""
        if self._handle:
            if unmap:
                self._mem = None  # type: ignore[assignment]
                self._view = None  # type: ignore[assignment]
            self._lib.shm_store_close(self._handle, 1 if unmap else 0)
            self._handle = None


class MemoryStore:
    """Per-worker in-memory store for small objects and pending task returns.

    Async-first: getters await an asyncio.Event per object, mirroring the
    reference memory store's GetAsync callback chain.
    """

    class _Waiter:
        __slots__ = ("event", "count")

        def __init__(self):
            self.event = asyncio.Event()
            self.count = 0

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self._loop = loop
        self._objects: Dict[ObjectID, SerializedObject] = {}
        self._events: Dict[ObjectID, "MemoryStore._Waiter"] = {}
        self._thread_events: Dict[ObjectID, list] = {}
        # Reentrant: the ids' `__hash__` is Python, so the cyclic GC can
        # run inside any critical section here, and an ObjectRef it frees
        # comes back through `_on_owned_ref_zero` -> `pop` on the same
        # thread. With a plain lock that thread (the IO loop's, in `put`)
        # waited for itself, and every `get` of the process with it.
        self._lock = threading.RLock()

    def put(self, object_id: ObjectID, obj: SerializedObject) -> None:
        with self._lock:
            self._objects[object_id] = obj
            waiter = self._events.pop(object_id, None)
            tevents = self._thread_events.pop(object_id, None)
        if waiter is not None:
            self._loop.call_soon_threadsafe(waiter.event.set)
        if tevents:
            for ev in tevents:
                ev.set()

    def get_blocking(self, object_id: ObjectID,
                     timeout: Optional[float] = None
                     ) -> Optional[SerializedObject]:
        """Block the CALLING thread until the object arrives — no event-loop
        round trip. Used by the sync `ray.get` fast path: the completing
        reply callback sets a plain threading.Event, so the driver's main
        thread wakes directly (one futex) instead of via
        run_coroutine_threadsafe + Task + concurrent.Future (three wakes).
        Returns None on timeout."""
        ev = threading.Event()
        with self._lock:
            obj = self._objects.get(object_id)
            if obj is not None:
                return obj
            self._thread_events.setdefault(object_id, []).append(ev)
        try:
            if not ev.wait(timeout):
                return None
        finally:
            with self._lock:
                lst = self._thread_events.get(object_id)
                if lst is not None:
                    try:
                        lst.remove(ev)
                    except ValueError:
                        pass
                    if not lst:
                        del self._thread_events[object_id]
        with self._lock:
            return self._objects.get(object_id)

    def get_if_exists(self, object_id: ObjectID) -> Optional[SerializedObject]:
        with self._lock:
            return self._objects.get(object_id)

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._objects

    async def get(self, object_id: ObjectID,
                  timeout: Optional[float] = None) -> SerializedObject:
        with self._lock:
            obj = self._objects.get(object_id)
            if obj is not None:
                return obj
            waiter = self._events.get(object_id)
            if waiter is None:
                waiter = MemoryStore._Waiter()
                self._events[object_id] = waiter
            waiter.count += 1
        try:
            await asyncio.wait_for(waiter.event.wait(), timeout)
        finally:
            with self._lock:
                waiter.count -= 1
                if waiter.count == 0 and self._events.get(object_id) is waiter:
                    del self._events[object_id]
        with self._lock:
            obj = self._objects.get(object_id)
        if obj is None:
            from ray_tpu.exceptions import ObjectLostError

            raise ObjectLostError(f"object {object_id} deleted while waiting")
        return obj

    def delete(self, object_id: ObjectID) -> None:
        with self._lock:
            obj = self._objects.pop(object_id, None)
        # Destroy outside the lock: a value holding ObjectRefs cascades into
        # ref-count callbacks that re-enter this store (the lock is
        # reentrant; other threads need not wait for the cascade).
        del obj

    def pop(self, object_id: ObjectID, default=None):
        """Remove and return the stored value (default when absent) — lets
        the owner's ref-zero path see WHAT it is deleting (inline value vs
        shm marker) and skip the arena/spill probes for inline objects.
        Pass a sentinel default to distinguish a stored None from absent
        (tasks returning None are common)."""
        with self._lock:
            return self._objects.pop(object_id, default)

    def size(self) -> int:
        with self._lock:
            return len(self._objects)


# ---------------------------------------------------------------------------
# Spilling (reference: src/ray/raylet/local_object_manager.h + external
# storage). Redesign: overflow spilling — an object that does not fit the
# arena is written to a per-node spill directory in the same framed format;
# readers (worker materialize + nodelet fetch) fall back to it transparently.
# ---------------------------------------------------------------------------
def spill_path(spill_dir: str, object_id: ObjectID) -> str:
    return os.path.join(spill_dir, object_id.hex())


def spill_write(spill_dir: str, object_id: ObjectID,
                obj: SerializedObject) -> str:
    # Chaos seam: injected failure behaves exactly like a full/readonly
    # spill disk (the write-then-rename below guarantees no torn file).
    from ray_tpu._private.chaos import get_chaos

    get_chaos().failpoint("object_store.spill")
    os.makedirs(spill_dir, exist_ok=True)
    path = spill_path(spill_dir, object_id)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(struct.pack(">I", len(obj.metadata)))
        f.write(obj.metadata)
        f.write(struct.pack(">I", len(obj.buffers)))
        for buf in obj.buffers:
            f.write(struct.pack(">Q", len(buf)))
            f.write(buf)
    os.replace(tmp, path)
    _spilled_objects_counter().inc()
    _spilled_bytes_counter().inc(float(obj.total_bytes()))
    return path


def spill_read(spill_dir: str, object_id: ObjectID
               ) -> Optional[SerializedObject]:
    path = spill_path(spill_dir, object_id)
    if not os.path.exists(path):
        return None
    with open(path, "rb") as f:
        data = f.read()
    off = 0
    (mlen,) = struct.unpack_from(">I", data, off); off += 4
    metadata = data[off:off + mlen]; off += mlen
    (nbuf,) = struct.unpack_from(">I", data, off); off += 4
    buffers = []
    for _ in range(nbuf):
        (blen,) = struct.unpack_from(">Q", data, off); off += 8
        buffers.append(data[off:off + blen]); off += blen
    return SerializedObject(bytes(metadata), buffers, [])


def spill_delete(spill_dir: str, object_id: ObjectID) -> None:
    try:
        os.remove(spill_path(spill_dir, object_id))
    except OSError:
        pass
