"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

`jax.profiler.ProfileData` reads the file with nothing but jaxlib. A TPU
shows as planes named `/device:TPU:<n>`, each with a line of executed HLO
operations (`XLA Ops`) and a line of whole programs (`XLA Modules`, one
event per launch, named `jit_<python function>(<id>)`). The benchmark's own
host spans (`jax.profiler.TraceAnnotation`, names starting with `bench.`)
are on the host plane, on the same clock.

busy    = length of the union of the op intervals inside the window
window  = the `bench.window` span the tracer thread holds open while the
          profiler runs; without one, first to last device event
idle gap = a stretch of the window in which no op ran; each is labelled
          with the innermost `bench.*` span that was open on the host at
          its midpoint, and gaps are summed by label."""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# A gap shorter than this is the space between back-to-back operations,
# not the device waiting for the host.
MIN_GAP_NS = 20_000

Interval = Tuple[float, float, str]


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> List[Interval]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def host_spans(data) -> List[Interval]:
    spans = []
    for plane in data.planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            spans.extend(ev for ev in _events(line)
                         if ev[2].startswith(SPAN_PREFIX))
    return spans


def _label(t: float, spans: List[Interval]) -> str:
    best: Optional[Interval] = None
    for a, b, name in spans:
        if a <= t <= b and name != WINDOW_SPAN:
            if best is None or (b - a) < (best[1] - best[0]):
                best = (a, b, name)
    return best[2] if best else "no bench span open"


def module_name(event_name: str) -> str:
    """`jit_decode(123456)` -> `jit_decode`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def describe(data, limit: int = 6) -> str:
    """A trace's planes, lines and first events, for reading by hand."""
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                stats = [(k, v) for k, v in e.stats][:8]
                out.append(f"    {e.name!r} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f} {stats}")
    return "\n".join(out)


def op_label(event_name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO instruction; keep its
    opcode and the type and shape of its (first) result, and drop the
    instruction's own number, so that the same operation of every layer
    falls under one label: `fusion bf16[16,14336]`."""
    name, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:80]
    if rest.startswith("("):            # a tuple result: skip to its end
        depth = end = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, tail = rest[:end + 1], rest[end + 1:]
    else:
        result, _, tail = rest.partition(" ")
    opcode = tail.strip().split("(", 1)[0]
    shape = re.sub(r"\{[^}]*\}", "", result)[:48]
    scope = re.sub(r"[.\d]+$", "", name.lstrip("%"))
    scope = "" if scope == opcode or scope in (
        "fusion", "copy", "bitcast") else f" ({scope})"
    return f"{opcode} {shape}{scope}"


def self_times(ops: List[Interval]) -> List[Interval]:
    """Events of the ops line nest (a `while` holds its body's operations);
    give each the time no child of it covers. Returns (start, self_ns,
    name)."""
    out: List[List[Any]] = []
    stack: List[int] = []
    for a, b, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and out[stack[-1]][3] <= a:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= b - a
        out.append([a, b - a, name, b])
        stack.append(len(out) - 1)
    return [(a, max(0.0, t), name) for a, t, name, _ in out]


def reduce(data, top: int = 10) -> Dict[str, Any]:
    """The reduction every trace-fed metric reads. Seconds throughout."""
    import bisect

    spans = host_spans(data)
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    devices = []
    for plane in data.planes:
        if not _is_device(plane.name):
            continue
        ops_line = _line(plane, OPS_LINE)
        if ops_line is None:
            continue
        mod_line = _line(plane, MODULES_LINE)
        devices.append((plane.name, _events(ops_line),
                        _events(mod_line) if mod_line is not None else []))
    if not devices or not any(ops for _, ops, _ in devices):
        return {}
    if windows:
        lo, hi = windows[0][0], windows[0][1]
    else:
        lo = min(ev[0] for _, ops, _ in devices for ev in ops)
        hi = max(ev[1] for _, ops, _ in devices for ev in ops)
    busy_ns = 0.0
    op_time: Dict[str, List[float]] = {}
    modules: Dict[str, Dict[str, float]] = {}
    gap_time: Dict[str, float] = {}
    for _, ops, mods in devices:
        inside = [ev for ev in ops if ev[1] > lo and ev[0] < hi]
        merged = union(_clip([(a, b) for a, b, _ in inside], lo, hi))
        busy_ns += sum(b - a for a, b in merged)
        mods = sorted(mods)
        starts = [m[0] for m in mods]
        for a, t, name in self_times(inside):
            i = bisect.bisect_right(starts, a) - 1
            where = (module_name(mods[i][2])
                     if i >= 0 and a < mods[i][1] else "?")
            slot = op_time.setdefault(f"{where}: {op_label(name)}",
                                      [0.0, 0])
            slot[0] += t
            slot[1] += 1
        for a, b, name in mods:
            if a >= lo and b <= hi:       # whole launches only
                m = modules.setdefault(module_name(name),
                                       {"count": 0, "seconds": 0.0})
                m["count"] += 1
                m["seconds"] += (b - a) / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= MIN_GAP_NS:
                label = _label((a + b) / 2, spans)
                gap_time[label] = gap_time.get(label, 0.0) + (b - a)
    n = len(devices)
    ops_ranked = sorted(op_time.items(), key=lambda kv: -kv[1][0])[:top]
    gaps_ranked = sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9 / n,
            "devices": n,
            "device_ops": [[f"{k} x{int(c)}", t / 1e9 / n]
                           for k, (t, c) in ops_ranked],
            "idle_gaps": [[k, t / 1e9 / n] for k, t in gaps_ranked],
            "modules": modules, "has_window_span": bool(windows)}
