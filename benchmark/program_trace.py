"""What the program says about itself inside a traced run.

The program (not the benchmark) opens spans named `ray_tpu.*` through
`ray_tpu._private.flight_recorder.span`/`mark`: while the profiler runs
they are events of the host plane, on the device trace's clock, and their
arguments (the span's counters) are the events' stats. Its Pallas kernels
carry a `name=`, which becomes the HLO instruction's own name: the `XLA
Ops` event of a call reads `%paged_decode.3 = ... custom-call(...)`.

`read(path)` opens one `.xplane.pb` (through `xplane.load`) and returns

    {"events":  [{"name", "start_ns", "duration_ns", "stats": {...}}, ...]
                the `ray_tpu.*` host events, by start time
     "kernels": {kernel: {"count": n, "seconds": s}}
                device time of custom-calls whose instruction name holds
                `kernel`, summed over device planes
     "window_s": length of the `bench.window` span (None without one)}

A program that opens no such span and names no kernel (the parent of the
PR that added them) gives empty lists: the readers then return None."""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Iterable, List, Optional

from benchmark import xplane

PROGRAM_PREFIX = "ray_tpu."
KERNELS = ("paged_decode", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# A reader with fewer events than this in the slice has nothing to average.
MIN_EVENTS = 5

def instruction_name(event_name: str) -> str:
    """`%paged_decode.3 = bf16[...] custom-call(...)` -> `paged_decode.3`."""
    name, sep, _ = event_name.partition(" = ")
    return name.lstrip("%") if sep else ""


def reduce(data) -> Dict[str, Any]:
    events: List[Dict[str, Any]] = []
    kernels: Dict[str, Dict[str, float]] = {}
    window_s: Optional[float] = None
    for plane in data.planes:
        if xplane._is_device(plane.name):
            line = xplane._line(plane, xplane.OPS_LINE)
            for e in (line.events if line is not None else ()):
                if "custom-call" not in e.name:
                    continue
                instr = instruction_name(e.name)
                for kernel in KERNELS:  # no name holds another

                    if kernel in instr:
                        k = kernels.setdefault(
                            kernel, {"count": 0, "seconds": 0.0})
                        k["count"] += 1
                        k["seconds"] += e.duration_ns / 1e9
                        break
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    events.append({"name": e.name, "start_ns": e.start_ns,
                                   "duration_ns": e.duration_ns,
                                   "stats": dict(e.stats)})
                elif e.name == xplane.WINDOW_SPAN and window_s is None:
                    window_s = e.duration_ns / 1e9
    events.sort(key=lambda ev: ev["start_ns"])
    return {"events": events, "kernels": kernels, "window_s": window_s}


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime: float) -> Dict[str, Any]:
    return reduce(xplane.load(path))


def read(path: str) -> Dict[str, Any]:
    """One trace file's reduction; eight readers share one parse."""
    return _read(path, os.path.getmtime(path))


def of(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The reduction of every trace of a traced run (one per replica)."""
    return [read(t["path"]) for t in obs.get("traces", [])
            if t.get("path") and os.path.isfile(t["path"])]


def events(obs: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    return [e for r in of(obs) for e in r["events"] if e["name"] == name]


def mean_stat(obs: Dict[str, Any], name: str, stat: str) -> Optional[float]:
    """Mean of argument `stat` over the events called `name`."""
    values = [float(e["stats"][stat]) for e in events(obs, name)
              if stat in e["stats"]]
    return sum(values) / len(values) if len(values) >= MIN_EVENTS else None


def kernel_seconds_per_call(obs: Dict[str, Any], names: Iterable[str],
                            per: Optional[str] = None) -> Optional[float]:
    """Device seconds of the kernels `names` together, per call of kernel
    `per` (the first of `names` if not given)."""
    names = list(names)
    per = per or names[0]
    seconds = calls = 0.0
    for r in of(obs):
        seconds += sum(r["kernels"].get(n, {}).get("seconds", 0.0)
                       for n in names)
        calls += r["kernels"].get(per, {}).get("count", 0)
    return seconds / calls if calls >= MIN_EVENTS else None
