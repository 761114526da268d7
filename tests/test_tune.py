"""ray_tpu.tune tests (reference strategy: python/ray/tune/tests — small
real-cluster experiments; PBT/ASHA behavior asserted on synthetic losses)."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import tune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_random_and_grid_search(ray_start_regular, tmp_path):
    def trainable(config):
        # Quadratic bowl: best at x=3.
        score = -(config["x"] - 3.0) ** 2 + config["bias"]
        tune.report({"score": score})

    tuner = tune.Tuner(
        trainable,
        param_space={"x": tune.uniform(0.0, 6.0),
                     "bias": tune.grid_search([0.0, 10.0])},
        tune_config=tune.TuneConfig(num_samples=4, metric="score",
                                    mode="max", seed=7),
        run_config=tune.TuneRunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 8  # 4 samples x 2 grid values
    best = grid.get_best_result()
    assert best.metrics["score"] > 5.0  # top bias group
    assert not grid.errors


def test_asha_stops_bad_trials(ray_start_regular, tmp_path):
    def trainable(config):
        import time as _t

        for step in range(20):
            tune.report({"acc": config["lr"] * (step + 1)})
            _t.sleep(0.05)  # interleave trials so rungs see competitors

    tuner = tune.Tuner(
        trainable,
        # Good trials first + limited concurrency: async SHA can only stop
        # a trial that reaches a rung AFTER better competitors recorded
        # there, so laggard-bad must follow leader-good.
        param_space={"lr": tune.grid_search([10.0, 1.0, 0.1, 0.01])},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max", max_concurrent_trials=2,
            scheduler=tune.ASHAScheduler(metric="acc", mode="max",
                                         grace_period=2,
                                         reduction_factor=2, max_t=20)),
        run_config=tune.TuneRunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    iters = {r.config["lr"]: len(r.metrics_history) for r in grid}
    assert iters[0.01] < 20  # the worst trial was stopped early
    assert sum(iters.values()) < 4 * 20
    best = grid.get_best_result()
    assert best.config["lr"] == 10.0


def test_pbt_mutates_and_exploits(ray_start_regular, tmp_path):
    """PBT across 8 trials: bad-lr trials must adopt (a perturbation of) a
    good trial's lr via checkpoint exploit (VERDICT item 8 criterion)."""

    def trainable(config):
        import ray_tpu.tune as tune

        ckpt = tune.get_checkpoint()
        start = 0
        if ckpt is not None:
            with open(os.path.join(ckpt.as_directory(), "state.json")) as f:
                start = json.load(f)["step"]
        lr = tune.get_config()["lr"]
        for step in range(start, 12):
            score = lr * 10 - abs(lr - 1.0)  # best near lr=1
            os.makedirs("/tmp/_pbt_ck", exist_ok=True)
            ckdir = f"/tmp/_pbt_ck/{os.getpid()}_{step}"
            os.makedirs(ckdir, exist_ok=True)
            with open(os.path.join(ckdir, "state.json"), "w") as f:
                json.dump({"step": step + 1}, f)
            tune.report({"score": score},
                        checkpoint=tune.Checkpoint(ckdir))

    lrs = [0.001, 0.01, 0.1, 1.0]
    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search(lrs + lrs)},  # 8 trials
        tune_config=tune.TuneConfig(
            metric="score", mode="max", max_concurrent_trials=4,
            scheduler=tune.PopulationBasedTraining(
                metric="score", mode="max", perturbation_interval=3,
                hyperparam_mutations={"lr": tune.choice(lrs)}, seed=3)),
        run_config=tune.TuneRunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 8
    final_lrs = [r.config["lr"] for r in grid]
    # At least one originally-bad trial moved its lr (exploit happened).
    assert final_lrs != lrs + lrs
    assert not grid.errors


RESUME_SCRIPT = """
import json, os, sys
import ray_tpu
from ray_tpu import tune

def trainable(config):
    import time
    ckpt = tune.get_checkpoint()
    start = 0
    if ckpt is not None:
        with open(os.path.join(ckpt.as_directory(), "s.json")) as f:
            start = json.load(f)["step"]
    for step in range(start, 6):
        d = os.path.join("/tmp/_resume_ck", f"{os.getpid()}_{step}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "s.json"), "w") as f:
            json.dump({"step": step + 1}, f)
        tune.report({"it": step + 1}, checkpoint=tune.Checkpoint(d))
        time.sleep(%(sleep)s)

info = ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
print("SESSION", info["session_dir"], flush=True)
tuner = %(tuner)s
grid = tuner.fit()
assert not grid.errors, grid.errors
assert all(r.metrics["it"] == 6 for r in grid)
print("RESUME_OK", flush=True)
ray_tpu.shutdown()
"""


def _terminate_cluster_of(session_dir):
    """A driver's GCS and nodelet run in sessions of their own and outlive
    a driver that is SIGKILLed: end them, or they keep their workers, cores
    and ports from every test after this one. Their command lines name the
    session's directory; an empty name would be in every command line."""
    assert (os.path.basename(session_dir).startswith("session_")
            and os.path.isdir(session_dir)), session_dir
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if session_dir.encode() in f.read():
                    os.kill(int(pid), signal.SIGTERM)
        except OSError:
            continue


def test_experiment_resume_after_kill(tmp_path):
    """Kill a running experiment; Tuner.restore finishes it from
    checkpoints (reference: experiment_state resume)."""
    exp = str(tmp_path / "exp1")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")

    first = RESUME_SCRIPT % {
        "sleep": "0.8",
        "tuner": ("tune.Tuner(trainable, param_space={'x': "
                  "tune.grid_search([1, 2])}, "
                  "tune_config=tune.TuneConfig(metric='it', mode='max'), "
                  f"run_config=tune.TuneRunConfig(storage_path={exp!r}, "
                  "name='e'))"),
    }
    p = subprocess.Popen([sys.executable, "-c", first], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         start_new_session=True)
    session_dir = ""
    for line in p.stdout:       # the log lines of init come first
        if line.startswith(b"SESSION "):
            session_dir = line.split()[1].decode()
            break
    state = os.path.join(exp, "e", "experiment_state.json")
    deadline = time.time() + 90
    # Wait until both trials have checkpointed at least once, then kill.
    def _progressed():
        if not os.path.exists(state):
            return False
        with open(state) as f:
            trials = json.load(f)["trials"]
        return (len(trials) == 2
                and all(t.get("checkpoint_path") for t in trials))

    while time.time() < deadline and not _progressed():
        time.sleep(0.3)
    assert _progressed(), "experiment never made progress"
    os.killpg(p.pid, signal.SIGKILL)
    p.wait()
    _terminate_cluster_of(session_dir)

    second = RESUME_SCRIPT % {
        "sleep": "0.05",
        "tuner": ("tune.Tuner.restore("
                  f"{os.path.join(exp, 'e')!r}, trainable)"),
    }
    out = subprocess.run([sys.executable, "-c", second], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "RESUME_OK" in out.stdout, out.stdout + out.stderr
