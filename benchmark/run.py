"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that starts the cluster, deploys (or trains), warms up,
measures for `--seconds`, shuts down, confirms the chip's holder is gone,
and prints one JSON object as the last line of stdout (README.md says what
it holds). This process never initializes a JAX backend: the chip belongs
to the replica or the train worker. There is no CPU mode: without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result. Tests relax the device check from outside (tests/benchmark_suite).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()   # set-up is counted from here

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PLATFORM = "tpu"
KINDS = {"serve_open": "serve_driver", "serve_closed": "serve_driver",
         "train": "train_driver"}


class NoChip(RuntimeError):
    pass


def say(line: str) -> None:
    print(line, flush=True)


def check_device(platform: str, count: int, want: int) -> None:
    if platform != PLATFORM:
        raise NoChip(f"the chip holder runs on {platform!r}, not "
                     f"{PLATFORM!r}")
    if count != want:
        raise NoChip(f"the chip holder computes on {count} device(s), "
                     f"wanted {want}")


def dump_worker_logs(session_dir: str) -> None:
    """A failed run leaves its reasons in the workers' logs."""
    import glob

    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "**",
                                              "*.log"), recursive=True),
                       key=os.path.getmtime):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-40:]
        if tail:
            print(f"--- {path}\n{''.join(tail)}", file=sys.stderr)


def context(manifest, cell: Dict[str, Any], seed: int, seconds: float,
            trace: bool) -> Dict[str, Any]:
    """What a driver is handed for one run of `cell`."""
    config = manifest.config(cell["config"])
    return {
        "manifest": manifest, "cell": cell, "config": config,
        "traffic": manifest.traffic(cell["traffic"]), "seed": seed,
        "seconds": seconds, "trace": trace, "t_process": T_PROCESS,
        "say": say, "platform": PLATFORM, "check_device": check_device,
        "model_kwargs": manifest.family(config["family"]).model_kwargs(
            config),
        # Generated files (tokenizer, traces): inside the checkout, at a
        # fixed path, git-ignored.
        "cache_dir": os.path.join(manifest.root, ".bench_cache"),
    }


def use_checkout_cache(manifest) -> None:
    """The compile cache: one fixed directory inside the checkout, which the
    program's workers take from the environment, and no size limit (a cell
    warms some 35 programs of 5-10 MB; under a limit smaller than one cell's
    programs an LRU cache evicts each before the next run asks for it, and
    every run compiles everything)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        manifest.root, ".jax_cache")
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)


def main(argv: Optional[List[str]] = None, root: Optional[str] = None
         ) -> int:
    from benchmark.manifest import Manifest

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = Manifest(root)
    cell = manifest.cell(args.workload)
    ctx = context(manifest, cell, args.seed, args.seconds, bool(args.trace))
    config, traffic = ctx["config"], ctx["traffic"]
    if traffic["kind"] not in KINDS:
        raise SystemExit(f"unknown workload kind {traffic['kind']!r}")

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and PLATFORM not in platforms.split(","):
        print(f"benchmark: no TPU: JAX_PLATFORMS={platforms} keeps JAX off "
              "it, and the benchmark has no CPU mode", file=sys.stderr)
        return 2
    from ray_tpu._private.accelerators import detect_resources

    found = int(detect_resources().get("TPU", 0))
    if found < cell["chips"]:
        print(f"benchmark: no TPU: this host exposes {found} chip(s), the "
              f"cell needs {cell['chips']}", file=sys.stderr)
        return 2

    import importlib

    driver = importlib.import_module("benchmark." + KINDS[traffic["kind"]])
    say(f"cell {cell['name']}: config {cell['config']} "
        f"({ctx['model_kwargs']}), traffic {cell['traffic']} "
        f"({traffic['kind']}), {cell['chips']} chip(s), seed {args.seed}, "
        f"{args.seconds}s, trace {args.trace}")
    use_checkout_cache(manifest)
    import ray_tpu

    ray_tpu.init()
    try:
        out = driver.run(ctx)
    except NoChip as e:
        print(f"benchmark: no TPU: {e}", file=sys.stderr)
        return 2
    except BaseException:
        from ray_tpu._private import worker as worker_mod

        dump_worker_logs(worker_mod.global_worker().session_dir)
        raise
    finally:
        ray_tpu.shutdown()
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        print("benchmark: this process initialized a JAX backend; it must "
              "stay off the chip", file=sys.stderr)
        return 1

    obs = out.pop("obs")
    e2e = out.pop("e2e")
    device = out["device"]
    line: Dict[str, Any] = {"correct": out["correct"],
                            "attempted": out["attempted"],
                            "failed": out["failed"]}
    if args.trace:
        obs.update(cell=cell, config=config, traffic=traffic,
                   model_kwargs=ctx["model_kwargs"],
                   peaks=manifest.peaks(device["kind"])
                   if device["platform"] == "tpu" else None,
                   family=manifest.family(config["family"]))
        line["metrics"] = manifest.layer_values(cell["name"], obs)
        traces = obs["traces"]
        if traces:
            device["busy_s"] = sum(t["busy_s"] for t in traces) / len(traces)
            device["window_s"] = traces[0]["window_s"]
            line["breakdown"] = {"device_ops": traces[0]["device_ops"],
                                 "idle_gaps": traces[0]["idle_gaps"]}
        elif PLATFORM == "tpu":
            print("benchmark: the traced run found no device operation in "
                  "its trace", file=sys.stderr)
            return 1
    else:
        units = {m["name"]: m["unit"]
                 for m in manifest.metrics_for(cell["name"], "end_to_end")}
        missing = set(units) - set(e2e)
        if missing:
            print(f"benchmark: no value for {sorted(missing)}",
                  file=sys.stderr)
            return 1
        line["metrics"] = {k: {"value": float(e2e[k]), "unit": units[k]}
                           for k in units}
    line["device"] = device
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
