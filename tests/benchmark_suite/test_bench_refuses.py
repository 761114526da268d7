"""The command has no CPU mode, and needs the program it measures."""

import os
import shutil
import subprocess
import sys

from bench_helpers import REPO

ARGS = ["--workload", "chat-steady", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env, timeout=120):
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py")] + ARGS,
        env=env, cwd=cwd, capture_output=True, text=True, timeout=timeout)


def _no_result(proc):
    assert proc.returncode != 0, proc.stdout
    assert '"correct"' not in proc.stdout, proc.stdout


def test_refuses_under_cpu_platform():
    proc = _run(REPO, dict(os.environ, JAX_PLATFORMS="cpu"))
    _no_result(proc)
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]


def test_refuses_without_chips():
    from ray_tpu._private.accelerators import _count_tpu_chips

    if _count_tpu_chips():
        return  # a host with chips: nothing to refuse
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS")}
    proc = _run(REPO, env, 300)
    _no_result(proc)
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]


def test_fails_where_only_the_benchmark_is(tmp_path):
    """BENCHMARK.json and the files under `paths`, nothing else: there is
    no program to measure, so no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    _no_result(_run(str(tmp_path), env))
