"""Each workload kind end to end at LlamaConfig.tiny on the CPU, with the
device check relaxed from outside. Slow: run by hand before chip time
(`pytest tests/benchmark_suite/test_bench_rehearsal.py -m slow`), not in
tier-1. Nothing these runs time is a device number."""

import json
import subprocess
import sys

import pytest

from bench_helpers import REPO, rehearsal_env, tiny_root


@pytest.mark.slow
@pytest.mark.parametrize("cell,trace", [
    ("tiny-open", 0), ("tiny-open", 1), ("tiny-closed", 0),
    ("tiny-closed", 1), ("tiny-train", 0), ("tiny-train", 1)])
def test_rehearsal_tiny_cpu(tmp_path, cell, trace):
    root = tiny_root(tmp_path)
    env, driver = rehearsal_env(tmp_path)
    proc = subprocess.run(
        [sys.executable, driver, root, "--workload", cell, "--seed",
         str(2 ** 31 + 11), "--seconds", "4", "--trace", str(trace)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-6000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, proc.stdout[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    with open(root + "/BENCHMARK.json") as f:
        manifest = json.load(f)
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]}
    got = set(last["metrics"])
    if trace:
        # A CPU trace has no device plane: the readers that need one
        # return nothing and are left out of the line.
        assert got and got <= want
    else:
        assert got == want
