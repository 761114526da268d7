"""BENCHMARK.json and the files it names, found by name under a root.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own:

    benchmark/configs/<config>.json         sizes as run, source, reduced
    benchmark/workloads/<traffic>.json      the traffic mix and its `kind`
    benchmark/layer_metrics/<metric>.py     read(obs) -> number or None
    benchmark/families/<family>.py          config keys -> model arguments

so a later PR adds a cell by adding files and manifest entries only."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = "benchmark"


class ManifestError(ValueError):
    pass


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_py(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ManifestError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root: Optional[str] = None):
        self.root = os.path.abspath(root or repo_root())
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data: Dict[str, Any] = json.load(f)
        self.cells = {w["name"]: w for w in self.data["workloads"]}
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.end_to_end = {m["name"]: m for m in self.data["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.data["per_layer"]}

    # -- lookups ---------------------------------------------------------
    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)

    def cell(self, name: str) -> Dict[str, Any]:
        if name not in self.cells:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(has {sorted(self.cells)})")
        return self.cells[name]

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration's file, as it is run."""
        with open(self.path(self.configs[name]["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> Dict[str, Any]:
        with open(self.path(BENCH_DIR, "workloads", name + ".json")) as f:
            return json.load(f)

    def family(self, name: str):
        return _load_py(self.path(BENCH_DIR, "families", name + ".py"),
                        f"_bench_family_{name}")

    def peaks(self, device_kind: str) -> Dict[str, float]:
        with open(self.path(BENCH_DIR, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table or device_kind.startswith("_"):
            raise ManifestError(
                f"device kind {device_kind!r} is not in peaks.json")
        return table[device_kind]

    def metrics_for(self, cell: str, group: str) -> List[Dict[str, Any]]:
        """Metrics of `group` ("end_to_end" or "per_layer") that `cell`
        reports: those without a `workloads` key, and those that list it."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Any]:
        """A per-layer metric's reader: read(obs) -> number, or None when
        it finds nothing to read."""
        path = self.path(BENCH_DIR, "layer_metrics", metric + ".py")
        return _load_py(path, "_bench_metric_" + metric.replace(".", "_")
                        ).read

    def layer_values(self, cell: str, obs: Dict[str, Any]
                     ) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for m in self.metrics_for(cell, "per_layer"):
            value = self.reader(m["name"])(obs)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


def check(manifest: Manifest) -> List[str]:
    """Everything the manifest names exists and agrees. Returns problems."""
    bad: List[str] = []
    m = manifest
    for name, c in m.configs.items():
        if not os.path.isfile(m.path(c["file"])):
            bad.append(f"config {name}: no file {c['file']}")
            continue
        cfg = m.config(name)
        for key in c["reduced"]:
            if key not in cfg.get("reduced", {}):
                bad.append(f"config {name}: reduced key {key!r} is not "
                           "explained in its file")
        fam = m.path(BENCH_DIR, "families", cfg.get("family", "") + ".py")
        if not os.path.isfile(fam):
            bad.append(f"config {name}: no family file {fam}")
    used = set()
    for name, w in m.cells.items():
        if w["config"] not in m.configs:
            bad.append(f"cell {name}: unknown config {w['config']}")
        used.add(w["config"])
        path = m.path(BENCH_DIR, "workloads", w["traffic"] + ".json")
        if not os.path.isfile(path):
            bad.append(f"cell {name}: no traffic file {path}")
        e2e = {x["name"] for x in m.metrics_for(name, "end_to_end")}
        if "setup_s" not in e2e or len(e2e) < 2:
            bad.append(f"cell {name}: reports {sorted(e2e)}")
        layer = m.metrics_for(name, "per_layer")
        if not layer:
            bad.append(f"cell {name}: no per-layer metric")
        for x in layer:
            if x["moves"] not in e2e:
                bad.append(f"{x['name']} moves {x['moves']}, which cell "
                           f"{name} does not report")
    for name in set(m.configs) - used:
        bad.append(f"config {name} is used by no cell")
    for group in ("end_to_end", "per_layer"):
        for x in m.data[group]:
            for cell in x.get("workloads", []):
                if cell not in m.cells:
                    bad.append(f"{x['name']}: unknown cell {cell}")
    for name in m.per_layer:
        path = m.path(BENCH_DIR, "layer_metrics", name + ".py")
        if not os.path.isfile(path):
            bad.append(f"per-layer metric {name}: no reader {path}")
    return bad
