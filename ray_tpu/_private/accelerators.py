"""Accelerator detection and per-process device steering — TPU first-class.

Counterpart of python/ray/_private/accelerators/tpu.py:110
(TPUAcceleratorManager) in the reference: count the host's chips, honor
TPU_VISIBLE_CHIPS, and advertise both per-chip "TPU" resources and a
pod-slice head resource ("TPU-<gen>-<topo>-head", reference tpu.py:15-61) so
placement groups can gang-schedule whole slices.

A chip belongs to one process at a time, so neither the driver nor the
nodelet ever initializes the TPU runtime: chips are counted from the device
files the host exposes, and `process_environ` decides, per worker process,
whether JAX sees the leased chips or the CPU only.
"""

from __future__ import annotations

import os
from typing import Dict, Mapping, Optional, Sequence, Tuple

_detect_cache: Optional[Dict[str, float]] = None


def _tpu_env_topology() -> Tuple[Optional[str], Optional[str]]:
    """(generation, topology) from env/metadata, e.g. ("v5e", "2x4")."""
    accel_type = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-8"
    if accel_type and "-" in accel_type:
        gen, _, count = accel_type.partition("-")
        gen = gen.replace("litepod", "e").replace("pod", "")
        return gen, count
    return None, None


def detect_resources(num_cpus: Optional[float] = None,
                     num_tpus: Optional[float] = None) -> Dict[str, float]:
    """Resources this host contributes to the cluster."""
    global _detect_cache
    resources: Dict[str, float] = {}
    if num_cpus is None:
        num_cpus = float(os.cpu_count() or 1)
    resources["CPU"] = float(num_cpus)

    if num_tpus is not None:
        tpu_count = float(num_tpus)
    else:
        visible = os.environ.get("RAY_TPU_TPU_VISIBLE_CHIPS") or os.environ.get(
            "TPU_VISIBLE_CHIPS"
        )
        if visible is not None:
            tpu_count = float(len([c for c in visible.split(",") if c.strip()]))
        elif _detect_cache is not None:
            tpu_count = _detect_cache.get("TPU", 0.0)
        else:
            tpu_count = float(_count_tpu_chips())
            _detect_cache = {"TPU": tpu_count}
    if tpu_count > 0:
        resources["TPU"] = tpu_count
        gen, topo = _tpu_env_topology()
        if gen and topo:
            # Worker 0 of a slice advertises the head resource for gang
            # scheduling (reference: tpu.py pod-slice naming).
            if os.environ.get("TPU_WORKER_ID", "0") == "0":
                resources[f"TPU-{gen}-{topo}-head"] = 1.0
    # Schedulable memory (reference: ray gives tasks/actors a `memory`
    # resource for admission control — enforcement is the memory monitor's
    # OOM policy, not a hard cap). 70% of MemTotal, like the reference's
    # default memory headroom.
    mem = _host_memory_bytes()
    if mem:
        resources["memory"] = float(int(mem * 0.7))
    return resources


def _host_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def _count_tpu_chips() -> int:
    """Chips this host exposes, from its device files: one VFIO group per
    chip (`/dev/vfio/<n>`, v5e and later) or one `/dev/accel<n>` (earlier
    generations). TPU_CHIPS_PER_HOST_BOUNDS is not consulted: it describes
    the host type, and a VM that was handed one chip of a four-chip host
    still carries the host's bounds."""
    def entries(path: str):
        try:
            return os.listdir(path)
        except OSError:
            return []

    return (sum(name.isdigit() for name in entries("/dev/vfio"))
            or sum(name.startswith("accel") for name in entries("/dev")))


# libtpu's chip grid for a process that owns n chips of one host
# (TPU_CHIPS_PER_PROCESS_BOUNDS; reference tpu.py has the same table).
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """Where a process that compiles for the chip keeps XLA's persistent
    compilation cache: JAX_COMPILATION_CACHE_DIR when set, else one fixed
    directory in the checkout. The path is part of the cache key, so it
    must not move between runs. Entry points that hold the chip themselves
    put this in their environment before importing jax; workers get it
    from `process_environ`."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache")


def process_environ(base: Mapping[str, str],
                    tpu_chips: Sequence[int] = ()) -> Dict[str, str]:
    """Environment for a process the runtime starts, derived from `base`.

    Without leased chips the process is held to the CPU platform, whatever
    `base` says: a data, rllib or CPU-train worker that imports jax must
    not take the chip from the worker that leased it. With leased chips it
    gets the TPU platform — even under a parent that set JAX_PLATFORMS=cpu
    to stay off the chip itself — restricted to exactly those chips, with
    the process bounds libtpu needs for several processes to share a host,
    and the compilation cache (a CPU process keeps `base`'s setting: its
    programs are small, and XLA:CPU reloads cached code with a
    machine-feature warning per entry).
    """
    env = dict(base)
    if not tpu_chips:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    bounds = _CHIP_BOUNDS.get(len(tpu_chips))
    if bounds is None:
        raise ValueError(
            f"no TPU process bounds known for {len(tpu_chips)} chips "
            f"(supported: {sorted(_CHIP_BOUNDS)})")
    env["JAX_PLATFORMS"] = "tpu,cpu"
    env["TPU_VISIBLE_CHIPS"] = ",".join(map(str, tpu_chips))
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
    env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir(base)
    return env
