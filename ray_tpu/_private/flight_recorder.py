"""Hot-path flight recorder: wire accounting, event-loop lag tracing, and
per-call overhead decomposition (reference: the reference runtime splits
this across core_worker transport stats, the object manager profile events,
and stats/metric_defs.h — here one always-on, low-overhead module).

Design constraints, in order:

1. The hot path (per-frame, per-call) must stay in the low-microsecond
   range: plain-int ``+=`` on module singletons, no locks, no metric-lock
   acquisition per frame. A background thread converts the accumulated
   deltas into real ``ray_tpu_*`` metrics every ~2s (the metrics plane
   then flushes them to the GCS on its own cadence).
2. Per-call decomposition is *sampled* (1-in-``RAY_TPU_FR_SAMPLE``) on the
   client; the server-side stamps it stitches against are cheap enough
   (~2 perf_counter_ns calls) to stay always-on.
3. Everything lands in one bounded ring buffer (``RAY_TPU_FR_RING``
   events) dumpable on demand: `ray_tpu debug flight-record`.
4. ``span``/``mark`` are the one way code above the RPC path (the LLM
   engine, the serve replica) times itself: a ring event, and the same
   interval in the JAX profiler's trace when this process has JAX. This
   module never imports JAX: the GCS and the nodelet stay without it.

Phase model for a call (all durations, never wall-clock pairs — so
cross-host clock skew cannot produce negative phases):

    serialize  spec/kwargs -> pickle-5 parts (client)
    frame      part assembly + header build   (client)
    syscall    writer.write()/sendall of the parts (client)
    dispatch   server receipt -> user code start (decode, queueing,
               executor hop; = server_total - exec)
    exec       user code                        (server)
    reply      reply delivery/result handling   (client)
    wire       everything unmeasured in between: kernel buffers, the
               network, the peer's read loop (= e2e - all of the above,
               clamped at 0) — the decomposition telescopes to e2e by
               construction.

Plain-int accumulation races (two threads interleaving ``+=``) can drop
the odd increment; that is deliberate — counters here are rates for
dashboards, not invoiced quantities, and the alternative is a lock in
``_frame_parts``.
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

_ENABLED = os.environ.get("RAY_TPU_FLIGHT_RECORDER", "1").lower() not in (
    "0", "false", "no")
# Default 1-in-16: the guard test budgets the whole recorder at 3% of
# sync-call latency and the sampled path (begin/finish/record_event) is
# its single biggest line item — at 2.5k calls/s this still yields ~150
# decomposition samples per second per function.
_SAMPLE_EVERY = max(1, int(os.environ.get("RAY_TPU_FR_SAMPLE", "16") or 16))
_RING_CAP = max(64, int(os.environ.get("RAY_TPU_FR_RING", "4096") or 4096))
_LAG_INTERVAL_S = float(os.environ.get("RAY_TPU_LOOP_LAG_INTERVAL_S",
                                       "0.25") or 0.25)
_STALL_THRESHOLD_S = float(os.environ.get("RAY_TPU_LOOP_STALL_MS",
                                          "50") or 50) / 1000.0
_PUBLISH_INTERVAL_S = 2.0

_PHASES = ("serialize", "frame", "syscall", "dispatch", "exec", "reply",
           "wire")

_KIND_LABELS = {0: "request", 1: "response", 2: "notify"}


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Test/bench hook: flip the recorder without re-importing."""
    global _ENABLED
    _ENABLED = bool(on)


# --------------------------------------------------------------------------
# Ring buffer (the "flight record"): bounded, lock-free (deque.append is
# atomic under the GIL), dumpable on demand.
# --------------------------------------------------------------------------

_ring: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=_RING_CAP)


def record_event(kind: str, **fields) -> None:
    fields["kind"] = kind
    fields["ts"] = time.time()
    _ring.append(fields)


def dump_events() -> List[Dict[str, Any]]:
    return list(_ring)


# --------------------------------------------------------------------------
# Program spans: `with span("ray_tpu.engine.admit", free_slots=3) as sp:`.
# One `kind="span"` ring event per span (name, start, duration, arguments)
# and, when this process has already imported JAX, a
# `jax.profiler.TraceAnnotation` of the same name and arguments around the
# body: while a profiler runs the span lands on the device trace's clock,
# and when none does the annotation costs a check (~0.5us).
# --------------------------------------------------------------------------


class _NoSpan:
    """The shared no-op both forms return with the recorder disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()


def _annotation(name: str, args: Dict[str, Any]):
    """A TraceAnnotation if JAX is already in this process, else None."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None mid-import, too
    if profiler is None:
        return None
    return profiler.TraceAnnotation(name, **args)


def _record_span(name: str, ts: float, dur_us: Optional[float],
                 args: Dict[str, Any]) -> None:
    """One ring event: start (wall clock), length (None for a mark)."""
    _ring.append({"kind": "span", "name": name, "ts": ts, "dur_us": dur_us,
                  "args": args, "thread": threading.current_thread().name})


class _Span:
    __slots__ = ("name", "args", "_ann", "_ts", "_t0")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = args
        self._ann = None

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self._ts = time.time()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args) -> None:
        """Arguments known only inside the body (how many were admitted,
        how many tokens came out): added to the ring event and to the
        profiler's span."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        dur_us = (time.perf_counter_ns() - self._t0) / 1000.0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _record_span(self.name, self._ts, dur_us, self.args)
        return False


def span(name: str, **args):
    """Context manager timing its body under `name`; `args` are the span's
    counters. `.set(**more)` adds what only the body learns."""
    if not _ENABLED:
        return _NO_SPAN
    return _Span(name, args)


def mark(name: str, **args) -> None:
    """The zero-length span, for events that do not nest on a thread (a
    request's first token, its release)."""
    if not _ENABLED:
        return
    ann = _annotation(name, args)
    if ann is not None:
        with ann:
            pass
    _record_span(name, time.time(), None, args)


class _Laps:
    """What `laps` returns: nanoseconds a part, and the last lap's end."""
    __slots__ = ("ns", "_t")

    def __init__(self, parts):
        self.ns = dict.fromkeys(parts, 0)
        self._t = time.perf_counter_ns()

    def lap(self, part: str) -> int:
        now = time.perf_counter_ns()
        d = now - self._t
        self._t = now
        self.ns[part] += d
        return d

    def ms(self) -> Dict[str, float]:
        return {f"{part}_ms": ns / 1e6 for part, ns in self.ns.items()}


class _NoLaps:
    """The shared no-op `laps` returns with the recorder disabled."""
    __slots__ = ()

    def lap(self, part: str) -> int:
        return 0

    def ms(self) -> Dict[str, float]:
        return {}


_NO_LAPS = _NoLaps()


def laps(*parts: str):
    """For a loop too hot for a span an iteration (a stream's items): a
    thread's time split among `parts`. `.lap(part)` gives `part` the time
    since the lap before it (the first, since this call) and returns it,
    so the parts sum to the whole; `.ms()` is `{part_ms: ...}`, the
    arguments of the one mark the loop ends in."""
    if not _ENABLED:
        return _NO_LAPS
    return _Laps(parts)


# --------------------------------------------------------------------------
# What the thread executing a task knows of it beyond its spec: when this
# process received the call (the `dispatch` phase's start) and, once the
# serve replica's handler has read it out of the call's context, the id of
# the request it serves. Marks carry that id as `rid`, so one string joins
# a request's events in every process it crosses; it is handed on whether
# the recorder is on or not.
# --------------------------------------------------------------------------

_task = threading.local()


def enter_task(entry_ns: int) -> None:
    """Executor thread, before user code runs: the worker's receipt stamp
    of the call (0 when it took none), and no request named yet."""
    _task.entry_ns = entry_ns
    _task.rid = ""


def task_entry_ns() -> int:
    return getattr(_task, "entry_ns", 0)


def set_request_id(rid: str) -> None:
    _task.rid = rid


def request_id() -> str:
    """The id of the request this thread is serving; "" outside one."""
    return getattr(_task, "rid", "")


# --------------------------------------------------------------------------
# Wire accounting: per-(kind, lane) tx/rx counters fed from rpc.py's frame
# build/read paths. Row layout keeps hot-path code to list-index increments.
# --------------------------------------------------------------------------

# (kind_label, lane) -> [frames, bytes, parts_built, parts_sent]
_wire_tx: Dict[tuple, List[int]] = {}
# (kind_label, lane) -> [frames, bytes]
_wire_rx: Dict[tuple, List[int]] = {}


_wire_sends: Dict[str, int] = {}


def wire_tx(kind: int, lane: str, nbytes: int, parts_built: int,
            parts_sent: int) -> None:
    """One call per outbound frame: frame/byte/part counters, the send-
    syscall count (== buffers after coalescing; a frame built is written
    exactly once), and the sampled size histogram. Fused into a single
    function on purpose — at ~2.5k calls/s on a 1-core host, each extra
    Python call on this path is measurable (see the guard test's 3%
    recorder-overhead budget)."""
    key = (_KIND_LABELS.get(kind, "other"), lane)
    row = _wire_tx.get(key)
    if row is None:
        row = _wire_tx.setdefault(key, [0, 0, 0, 0])
    row[0] += 1
    row[1] += nbytes
    row[2] += parts_built
    row[3] += parts_sent
    _wire_sends[lane] = _wire_sends.get(lane, 0) + parts_sent
    if not (row[0] % _SAMPLE_EVERY):
        note_frame_bytes("tx", nbytes)


def wire_sends(lane: str, n: int) -> None:
    """Count extra write()/sendall calls not tied to a frame build (the
    normal per-frame sends are folded into wire_tx)."""
    _wire_sends[lane] = _wire_sends.get(lane, 0) + n


def wire_rx(kind: int, lane: str, nbytes: int) -> None:
    key = (_KIND_LABELS.get(kind, "other"), lane)
    row = _wire_rx.get(key)
    if row is None:
        row = _wire_rx.setdefault(key, [0, 0])
    row[0] += 1
    row[1] += nbytes
    if not (row[0] % _SAMPLE_EVERY):
        note_frame_bytes("rx", nbytes)


def wire_summary() -> Dict[str, Any]:
    out: Dict[str, Any] = {"tx": {}, "rx": {},
                           "send_calls": dict(_wire_sends)}
    for (kind, lane), row in sorted(_wire_tx.items()):
        out["tx"][f"{kind}/{lane}"] = {
            "frames": row[0], "bytes": row[1], "parts_built": row[2],
            "parts_sent": row[3],
            "coalesce_ratio": round(row[2] / row[3], 2) if row[3] else None,
        }
    for (kind, lane), row in sorted(_wire_rx.items()):
        out["rx"][f"{kind}/{lane}"] = {"frames": row[0], "bytes": row[1]}
    return out


# --------------------------------------------------------------------------
# Directly-observed histograms (low-rate paths only). Lazily bound: the
# metrics plane must not be imported at module import time — worker/nodelet
# import order mirrors object_store.py's lazy-factory idiom.
# --------------------------------------------------------------------------

_hists: Dict[str, Any] = {}

_US_BOUNDARIES = tuple(v / 1e6 for v in (
    1, 5, 10, 25, 50, 100, 250, 500, 1000, 5000, 25_000, 100_000))
_BYTE_BOUNDARIES = (256.0, 1024.0, 4096.0, 16384.0, 65536.0, 262144.0,
                    float(1 << 20), float(1 << 22), float(1 << 24),
                    float(1 << 26))


def _hist(name: str, desc: str, boundaries, tag_keys=()) -> Optional[Any]:
    h = _hists.get(name)
    if h is None:
        try:
            from ray_tpu.util import metrics as um
            h = um.get_histogram(name, desc, boundaries=boundaries,
                                 tag_keys=tuple(tag_keys))
            _hists[name] = h
        except Exception:  # noqa: BLE001 - too early in process bring-up
            return None
    return h


_frame_sample = itertools.count()


def note_frame_bytes(direction: str, nbytes: int) -> None:
    # Sampled 1-in-N: a histogram observe takes the metric lock (~1µs) and
    # this is called for every frame in both directions; the sampled size
    # distribution is statistically identical.
    if next(_frame_sample) % _SAMPLE_EVERY:
        return
    h = _hist("ray_tpu_rpc_frame_bytes", "RPC frame size (bytes)",
              _BYTE_BOUNDARIES, ("direction",))
    if h is not None:
        h.observe(float(nbytes), tags={"direction": direction})


_batch_sample = itertools.count()


def note_batch(path: str, n: int) -> None:
    # Sampled 1-in-N: this runs per push batch (== per call for sync
    # workloads) and a histogram observe costs ~2µs of metric lock.
    if next(_batch_sample) % _SAMPLE_EVERY:
        return
    h = _hist("ray_tpu_rpc_batch_size",
              "Calls coalesced per push batch frame",
              (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0), ("path",))
    if h is not None:
        h.observe(float(n), tags={"path": path})


_exec_sample = itertools.count()


def note_exec(fn: str, exec_ns: int) -> None:
    """Server-side sampled exec span. The client's sampled call record
    lives in a different process, so this is what lets a worker's ring
    tell its half of the story in the merged flight-record trace."""
    if next(_exec_sample) % _SAMPLE_EVERY:
        return
    record_event("exec", fn=fn, exec_us=round(exec_ns / 1000.0, 1))


def _drain_stall_hist() -> Optional[Any]:
    return _hist("ray_tpu_rpc_drain_stall_seconds",
                 "Time awaiting transport drain (write backpressure)",
                 (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0))


def note_drain_stall(seconds: float) -> None:
    """Write-queue drain backpressure: how long _write_frame waited for the
    kernel buffer (anything visible here means the peer is not keeping up)."""
    h = _drain_stall_hist()
    if h is not None:
        h.observe(seconds)
    if seconds >= 0.005:
        record_event("drain_stall", seconds=round(seconds, 4))


# --------------------------------------------------------------------------
# Per-call overhead decomposition.
# --------------------------------------------------------------------------

_sample_counter = itertools.count()
_generic_sample = itertools.count()


def maybe_sample() -> bool:
    """Shared 1-in-RAY_TPU_FR_SAMPLE decision for instrumentation that is
    too hot to stamp every operation (e.g. per-ref store gets)."""
    return not (next(_generic_sample) % _SAMPLE_EVERY)
# fn -> deque of per-call phase dicts (µs)
_calls: Dict[str, "collections.deque"] = {}
_CALLS_WINDOW = 2048


def maybe_begin_call(fn: str) -> Optional[Dict[str, Any]]:
    """Start a sampled per-call record, or None when this call isn't
    sampled. itertools.count() is C-level and effectively atomic."""
    if not _ENABLED:
        return None
    if next(_sample_counter) % _SAMPLE_EVERY:
        return None
    return {"fn": fn, "t0": time.perf_counter_ns()}


_overhead_hist_sample = itertools.count()


def finish_call(rec: Dict[str, Any], *, server_ns: int = 0,
                exec_ns: int = 0, reply_ns: int = 0, n: int = 1) -> None:
    """Close a sampled record. Batch frames amortize: every phase (and e2e)
    divides by n, so the telescoping e2e = sum(phases) survives."""
    e2e = time.perf_counter_ns() - rec["t0"]
    ser = rec.get("serialize_ns", 0) + rec.get("pre_serialize_ns", 0)
    frame = rec.get("frame_ns", 0)
    sysc = rec.get("syscall_ns", 0)
    if server_ns and exec_ns > server_ns:
        exec_ns = server_ns
    dispatch = max(server_ns - exec_ns, 0)
    wire = max(e2e - ser - frame - sysc - server_ns - reply_ns, 0)
    k = 1000.0 * max(n, 1)  # ns -> µs, amortized per call
    sample = {
        "serialize": ser / k, "frame": frame / k, "syscall": sysc / k,
        "dispatch": dispatch / k, "exec": exec_ns / k, "reply": reply_ns / k,
        "wire": wire / k, "e2e": e2e / k,
    }
    fn = rec["fn"]
    dq = _calls.get(fn)
    if dq is None:
        dq = _calls.setdefault(
            fn, collections.deque(maxlen=_CALLS_WINDOW))
    dq.append(sample)
    record_event("call", fn=fn, n=n,
                 **{p: round(v, 1) for p, v in sample.items()})
    # Seven per-phase observes take ~7µs of metric lock; feed the metrics
    # plane from every 4th sampled call. The ring event and the _calls
    # window above keep full per-sample fidelity for overhead_breakdown().
    if next(_overhead_hist_sample) % 4:
        return
    h = _hist("ray_tpu_call_overhead_seconds",
              "Per-call overhead decomposition by phase",
              _US_BOUNDARIES, ("phase",))
    if h is not None:
        for p in _PHASES:
            h.observe(sample[p] / 1e6, tags={"phase": p})


def finish_call_from_reply(rec: Dict[str, Any], reply: Any,
                           reply_ns: int = 0) -> None:
    """Stitch the server-side stamps (_frs = total server ns, _frx = exec
    ns, attached by the executing worker) into a sampled client record."""
    if not isinstance(reply, dict):
        finish_call(rec, reply_ns=reply_ns)
        return
    items = reply.get("replies")
    if isinstance(items, list):  # batch frame
        exec_ns = sum(it.get("_frx", 0) for it in items
                      if isinstance(it, dict))
        finish_call(rec, server_ns=reply.get("_frs", 0), exec_ns=exec_ns,
                    reply_ns=reply_ns, n=max(1, len(items)))
    else:
        finish_call(rec, server_ns=reply.get("_frs", 0),
                    exec_ns=reply.get("_frx", 0), reply_ns=reply_ns)


def _pct(sorted_vals: List[float], q: float) -> float:
    return sorted_vals[int(q * (len(sorted_vals) - 1))]


def overhead_breakdown() -> Dict[str, Any]:
    """{fn: {phase: {count, mean_us, p50_us, p95_us, max_us}}} over the
    sampled-call window. Phases telescope: sum of per-phase means == the
    e2e mean (wire is the measured remainder)."""
    out: Dict[str, Any] = {}
    for fn, dq in sorted(_calls.items()):
        rows = list(dq)
        if not rows:
            continue
        agg: Dict[str, Any] = {}
        for ph in _PHASES + ("e2e",):
            vals = sorted(r.get(ph, 0.0) for r in rows)
            agg[ph] = {
                "count": len(vals),
                "mean_us": round(sum(vals) / len(vals), 1),
                "p50_us": round(_pct(vals, 0.5), 1),
                "p95_us": round(_pct(vals, 0.95), 1),
                "max_us": round(vals[-1], 1),
            }
        covered = sum(agg[ph]["mean_us"] for ph in _PHASES)
        e2e_mean = agg["e2e"]["mean_us"]
        agg["coverage"] = round(covered / e2e_mean, 3) if e2e_mean else None
        out[fn] = agg
    return out


def reset_calls() -> None:
    """Bench/test hook: drop the sampled-call window (e.g. between bench
    phases so each row's decomposition reflects only its own calls)."""
    _calls.clear()


# --------------------------------------------------------------------------
# Event-loop lag sampler + stall watchdog.
#
# A self-rescheduling call_later tick measures scheduling lag (actual fire
# time minus expected); the shared background thread watches the ticks'
# heartbeats and, when one goes stale past RAY_TPU_LOOP_STALL_MS, samples
# the loop thread's *current* stack via sys._current_frames() — catching
# the offending callback in the act, which post-hoc profiling cannot.
# --------------------------------------------------------------------------


class _LoopMonitor:
    __slots__ = ("name", "loop", "thread_id", "expected_mono",
                 "heartbeat_mono", "lags", "unpublished", "max_lag",
                 "stalled", "stalls")

    def __init__(self, loop, name: str):
        self.name = name
        self.loop = loop
        self.thread_id = 0
        self.expected_mono = 0.0
        self.heartbeat_mono = 0.0
        self.lags = collections.deque(maxlen=512)  # rolling, for summaries
        self.unpublished: List[float] = []  # drained by the publisher
        self.max_lag = 0.0
        self.stalled = False
        self.stalls = 0


_loops: Dict[int, _LoopMonitor] = {}
_loops_lock = threading.Lock()


def attach_loop(loop, name: str) -> None:
    """Install the lag sampler on an asyncio loop (safe pre-run: the first
    tick arms via call_soon_threadsafe and fires once the loop runs)."""
    if not _ENABLED:
        return
    key = id(loop)
    with _loops_lock:
        if key in _loops:
            return
        mon = _LoopMonitor(loop, name)
        _loops[key] = mon

    def _tick():
        now = time.monotonic()
        mon.thread_id = threading.get_ident()
        lag = max(0.0, now - mon.expected_mono)
        mon.lags.append(lag)
        mon.unpublished.append(lag)
        if lag > mon.max_lag:
            mon.max_lag = lag
        mon.heartbeat_mono = now
        mon.stalled = False
        mon.expected_mono = now + _LAG_INTERVAL_S
        loop.call_later(_LAG_INTERVAL_S, _tick)

    def _arm():
        mon.thread_id = threading.get_ident()
        now = time.monotonic()
        mon.heartbeat_mono = now
        mon.expected_mono = now + _LAG_INTERVAL_S
        loop.call_later(_LAG_INTERVAL_S, _tick)

    try:
        loop.call_soon_threadsafe(_arm)
    except RuntimeError:  # loop already closed
        with _loops_lock:
            _loops.pop(key, None)
        return
    _ensure_thread()


def _stack_of(thread_id: int) -> List[str]:
    frame = sys._current_frames().get(thread_id)
    if frame is None:
        return []
    return [f"{fs.filename.rsplit('/', 1)[-1]}:{fs.lineno}:{fs.name}"
            for fs in traceback.extract_stack(frame)[-12:]]


def loop_lag_summary() -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    with _loops_lock:
        mons = list(_loops.values())
    for mon in mons:
        vals = sorted(mon.lags)
        if not vals:
            continue
        out[mon.name] = {
            "samples": len(vals),
            "p50_ms": round(_pct(vals, 0.5) * 1000, 3),
            "p95_ms": round(_pct(vals, 0.95) * 1000, 3),
            "max_ms": round(vals[-1] * 1000, 3),
            "stalls": mon.stalls,
        }
    return out


# --------------------------------------------------------------------------
# Background thread: loop-stall watchdog + metrics publisher.
# --------------------------------------------------------------------------

_thread_lock = threading.Lock()
_thread_started = False

_published_tx: Dict[tuple, List[int]] = {}
_published_rx: Dict[tuple, List[int]] = {}
_published_sends: Dict[str, int] = {}
_published_stalls: Dict[str, int] = {}
_metrics: Dict[str, Any] = {}


def _ensure_thread() -> None:
    global _thread_started
    with _thread_lock:
        if _thread_started:
            return
        _thread_started = True
    t = threading.Thread(target=_run, name="ray_tpu_flight_recorder",
                         daemon=True)
    t.start()


def _watch_loops() -> None:
    now = time.monotonic()
    with _loops_lock:
        mons = list(_loops.items())
    for key, mon in mons:
        if mon.loop.is_closed():
            with _loops_lock:
                _loops.pop(key, None)
            continue
        if (mon.heartbeat_mono and not mon.stalled
                and mon.loop.is_running()
                and now - mon.heartbeat_mono
                > _LAG_INTERVAL_S + _STALL_THRESHOLD_S):
            # One event per stall episode: the next successful tick
            # clears .stalled.
            mon.stalled = True
            mon.stalls += 1
            held = now - mon.heartbeat_mono - _LAG_INTERVAL_S
            record_event("loop_stall", loop=mon.name,
                         held_s=round(held, 4),
                         stack=_stack_of(mon.thread_id))


def _publisher_metrics():
    """Create the publisher-fed metrics once (first publish)."""
    if _metrics:
        return _metrics
    from ray_tpu.util import metrics as um

    # Registered empty: a healthy cluster never stalls a writer, and the
    # dashboards' panel must still find the series' name.
    _drain_stall_hist()

    _metrics.update({
        "frames": um.get_counter(
            "ray_tpu_rpc_frames_total", "RPC frames by kind/lane/direction",
            tag_keys=("kind", "lane", "direction")),
        "bytes": um.get_counter(
            "ray_tpu_rpc_bytes_total", "RPC bytes by kind/lane/direction",
            tag_keys=("kind", "lane", "direction")),
        "parts": um.get_counter(
            "ray_tpu_rpc_parts_total",
            "Frame parts before (built) and after (sent) coalescing",
            tag_keys=("stage", "lane")),
        "syscalls": um.get_counter(
            "ray_tpu_rpc_send_syscalls_total",
            "write()/sendall calls issued for outbound frames",
            tag_keys=("lane",)),
        "coalesce": um.get_gauge(
            "ray_tpu_rpc_coalesce_ratio",
            "parts built / buffers sent (higher = better coalescing)",
            tag_keys=("lane",)),
        "lag": um.get_histogram(
            "ray_tpu_loop_lag_seconds",
            "Event-loop scheduling lag per sampler tick",
            boundaries=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
                        1.0, 5.0),
            tag_keys=("loop",)),
        "lag_max": um.get_gauge(
            "ray_tpu_loop_lag_max_seconds",
            "Max event-loop lag in the publish window",
            tag_keys=("loop",)),
        "stalls": um.get_counter(
            "ray_tpu_loop_stalls_total",
            "Loop stalls exceeding RAY_TPU_LOOP_STALL_MS",
            tag_keys=("loop",)),
    })
    return _metrics


def _publish() -> None:
    m = _publisher_metrics()
    for key, row in list(_wire_tx.items()):
        kind, lane = key
        prev = _published_tx.setdefault(key, [0, 0, 0, 0])
        d = [row[i] - prev[i] for i in range(4)]
        _published_tx[key] = list(row)
        tags = {"kind": kind, "lane": lane, "direction": "tx"}
        if d[0]:
            m["frames"].inc(d[0], tags=tags)
        if d[1]:
            m["bytes"].inc(d[1], tags=tags)
        if d[2]:
            m["parts"].inc(d[2], tags={"stage": "built", "lane": lane})
        if d[3]:
            m["parts"].inc(d[3], tags={"stage": "sent", "lane": lane})
        if row[3]:
            m["coalesce"].set(round(row[2] / row[3], 3),
                              tags={"lane": lane})
    for lane, total in list(_wire_sends.items()):
        d = total - _published_sends.get(lane, 0)
        _published_sends[lane] = total
        if d:
            m["syscalls"].inc(d, tags={"lane": lane})
    for key, row in list(_wire_rx.items()):
        kind, lane = key
        prev = _published_rx.setdefault(key, [0, 0])
        d = [row[i] - prev[i] for i in range(2)]
        _published_rx[key] = list(row)
        tags = {"kind": kind, "lane": lane, "direction": "rx"}
        if d[0]:
            m["frames"].inc(d[0], tags=tags)
        if d[1]:
            m["bytes"].inc(d[1], tags=tags)
    with _loops_lock:
        mons = list(_loops.values())
    for mon in mons:
        drained, mon.unpublished = mon.unpublished, []
        for lag in drained:
            m["lag"].observe(lag, tags={"loop": mon.name})
        m["lag_max"].set(round(mon.max_lag, 6), tags={"loop": mon.name})
        mon.max_lag = 0.0
        prev = _published_stalls.get(mon.name, 0)
        if mon.stalls > prev:
            m["stalls"].inc(mon.stalls - prev, tags={"loop": mon.name})
            _published_stalls[mon.name] = mon.stalls


KV_PREFIX = "fr:driver:"
KV_FRESH_S = 20.0


def _kv_export() -> None:
    """Park this driver's budget in GCS KV so the CLI / dashboard —
    separate processes that cannot RPC into a driver (drivers connect
    out, they don't listen) — can still report it. Workers are skipped:
    the per-node gather already reaches them directly."""
    import json

    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker_or_none()
    if w is None or w.mode != "driver":
        return
    bd = overhead_breakdown()
    if not bd:
        return
    payload = json.dumps({
        "ts": time.time(), "pid": os.getpid(),
        "breakdown": bd, "wire": wire_summary(),
        "loops": loop_lag_summary(),
        "events": dump_events()[-512:],
    }, default=str).encode()
    w._gcs_call_sync("kv_put", key=f"{KV_PREFIX}{os.getpid()}",
                     value=payload, overwrite=True)


def _run() -> None:
    # Floor of 100ms: every process runs this thread, and on small hosts
    # sub-50ms wakeups across N processes steal measurable GIL/CPU time
    # from the hot path. Stalls shorter than the tick still show up in
    # the lag histogram (the tick that finally fires records the lag);
    # only the in-the-act stack capture needs the stall to outlast a tick.
    tick = min(max(_STALL_THRESHOLD_S, 0.1), 0.5)
    last_publish = time.monotonic()
    while True:
        time.sleep(tick)
        try:
            _watch_loops()
        except Exception:  # noqa: BLE001 - watchdog must never die
            pass
        if time.monotonic() - last_publish >= _PUBLISH_INTERVAL_S:
            last_publish = time.monotonic()
            try:
                _publish()
            except Exception:  # noqa: BLE001
                pass
            try:
                _kv_export()
            except Exception:  # noqa: BLE001 - no GCS yet / shutdown race
                pass


def publish_now() -> None:
    """Test hook: force one publisher pass synchronously."""
    _publish()
    try:
        _kv_export()
    except Exception:  # noqa: BLE001
        pass


# --------------------------------------------------------------------------
# Snapshots + chrome trace export.
# --------------------------------------------------------------------------


def flight_snapshot() -> Dict[str, Any]:
    return {
        "pid": os.getpid(),
        "enabled": _ENABLED,
        "wire": wire_summary(),
        "loops": loop_lag_summary(),
        "events": dump_events(),
    }


def chrome_trace_events(events: Optional[List[Dict[str, Any]]] = None,
                        pid: Optional[Any] = None) -> List[Dict[str, Any]]:
    """Render ring events as chrome://tracing rows mergeable with
    state.timeline() task/phase spans (same X/i event grammar)."""
    rows: List[Dict[str, Any]] = []
    p = pid if pid is not None else f"flight-{os.getpid()}"
    for ev in (dump_events() if events is None else events):
        kind = ev.get("kind")
        ts_us = ev.get("ts", 0.0) * 1e6
        if kind == "call":
            dur = max(float(ev.get("e2e", 0.0)), 0.0)
            args = {k: ev[k] for k in _PHASES if k in ev}
            args["n"] = ev.get("n", 1)
            rows.append({"name": f"call:{ev.get('fn', '?')}",
                         "cat": "FLIGHT", "ph": "X",
                         "ts": ts_us - dur, "dur": dur,
                         "pid": p, "tid": "calls", "args": args})
        elif kind == "loop_stall":
            dur = max(float(ev.get("held_s", 0.0)) * 1e6, 0.0)
            rows.append({"name": f"loop_stall:{ev.get('loop', '?')}",
                         "cat": "FLIGHT", "ph": "X",
                         "ts": ts_us - dur, "dur": dur,
                         "pid": p, "tid": "loops",
                         "args": {"stack": ev.get("stack", [])}})
        elif kind == "exec":
            dur = max(float(ev.get("exec_us", 0.0)), 0.0)
            rows.append({"name": f"exec:{ev.get('fn', '?')}",
                         "cat": "FLIGHT", "ph": "X",
                         "ts": ts_us - dur, "dur": dur,
                         "pid": p, "tid": "exec",
                         "args": {"exec_us": ev.get("exec_us", 0.0)}})
        elif kind == "store_put":
            dur = max(float(ev.get("total_us", 0.0)), 0.0)
            args = {k: v for k, v in ev.items()
                    if k not in ("kind", "ts")}
            rows.append({"name": "store_put", "cat": "FLIGHT", "ph": "X",
                         "ts": ts_us - dur, "dur": dur,
                         "pid": p, "tid": "store", "args": args})
        elif kind == "span":
            # Start and length on the row itself, one chrome thread per
            # Python thread: spans of one thread nest as they did.
            row = {"name": ev.get("name", "?"), "cat": "FLIGHT",
                   "ts": ts_us, "pid": p,
                   "tid": ev.get("thread", "spans"),
                   "args": dict(ev.get("args") or {})}
            if ev.get("dur_us") is None:  # a mark
                row.update(ph="i", s="t")
            else:
                row.update(ph="X", dur=max(float(ev["dur_us"]), 0.0))
            rows.append(row)
        else:
            rows.append({"name": kind or "event", "cat": "FLIGHT",
                         "ph": "i", "ts": ts_us, "s": "p",
                         "pid": p, "tid": "events",
                         "args": {k: v for k, v in ev.items()
                                  if k not in ("kind", "ts")}})
    return rows


# --------------------------------------------------------------------------
# Fork safety: a child inherits the parent's module state but not its
# threads or loops. Mirror metrics._reset_after_fork.
# --------------------------------------------------------------------------


def _reset_after_fork() -> None:
    global _thread_started
    _thread_started = False
    _loops.clear()
    _ring.clear()
    _calls.clear()
    _wire_tx.clear()
    _wire_rx.clear()
    _wire_sends.clear()
    _published_tx.clear()
    _published_rx.clear()
    _published_sends.clear()
    _published_stalls.clear()
    _metrics.clear()
    _hists.clear()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reset_after_fork)
