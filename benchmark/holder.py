"""What both chip holders (the serve replica, the train worker) do for the
benchmark from inside their process: count compilations, trace a slice of
the window, read the device's memory. Only the process that holds the chip
can do these."""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts programs that went to the backend compiler (a cache hit
    included: loading one stalls a request as a compile does, only shorter)
    through `jax.monitoring`, and the persistent cache's hits and misses."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.programs = 0
        self.names: List[str] = []      # `fun_name` of each, in order
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw: Any) -> None:
        if name == BACKEND_COMPILE:
            self.programs += 1
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds += secs

    def _event(self, name: str, **_: Any) -> None:
        if name == CACHE_HIT:
            self.hits += 1
        elif name == CACHE_MISS:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"programs": self.programs, "seconds": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


def cache_everything() -> None:
    """Keep every program in the persistent cache, however fast it
    compiled: JAX's default leaves out those under a second, and a later
    run would compile them again during set-up."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def cache_entries() -> int:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def device_report() -> Dict[str, Any]:
    import jax

    devices = jax.local_devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak,
            "pid": os.getpid()}


def slice_options(cache_dir: str, seconds: float) -> Dict[str, Any]:
    """Which slice of a window of `seconds` a traced run traces: up to 4 s
    from just before its middle, into a fixed directory."""
    return {"dir": os.path.join(cache_dir, "trace"),
            "delay_s": max(0.2, 0.45 * seconds),
            "length_s": min(4.0, max(0.5, seconds / 3.0))}


class SliceTracer:
    """Traces `length_s` of the window, starting `delay_s` after `start()`,
    on a thread of its own; `finish()` waits for it and reduces the trace.
    The profiler runs with Python tracing off (it slows the host it is
    measuring) and host annotations on (the benchmark's own spans)."""

    def __init__(self, log_dir: str, delay_s: float, length_s: float):
        self.log_dir, self.delay_s, self.length_s = log_dir, delay_s, length_s
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-tracer")
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.delay_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    time.sleep(self.length_s)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by finish()
            self._error = e

    def finish(self) -> Dict[str, Any]:
        from benchmark import xplane

        assert self._thread is not None
        self._thread.join(self.delay_s + self.length_s + 300)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self._error is not None:
            raise RuntimeError(f"tracing failed: {self._error!r}")
        path = xplane.find_trace(self.log_dir)
        out = xplane.reduce(xplane.load(path))
        out["path"] = path
        return out


def span(name: str):
    """A host span in the profiler's own trace; free when it is off."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def wrap_with_span(obj: Any, method: str, name: str) -> None:
    """Open span `name` around every call of obj.method (the benchmark's
    spans go around calls into a layer, not inside it)."""
    inner = getattr(obj, method)

    def traced(*args: Any, **kwargs: Any):
        with span(name):
            return inner(*args, **kwargs)

    setattr(obj, method, traced)
