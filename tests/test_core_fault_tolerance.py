"""Fault tolerance: task retries, actor restarts, death detection (reference:
python/ray/tests/test_actor_failures.py, test_task_retries)."""

import os
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_task_retry_on_worker_crash(ray_start_regular, tmp_path):
    marker = str(tmp_path / "flaky_marker")

    @ray_tpu.remote
    def flaky(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)
        return "survived"

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == "survived"


def test_task_no_retry_exhausted(ray_start_regular):
    @ray_tpu.remote(max_retries=0)
    def die():
        os._exit(1)

    with pytest.raises(ray_tpu.RayTpuError):
        ray_tpu.get(die.remote(), timeout=60)


def test_actor_restart(ray_start_regular):
    @ray_tpu.remote
    class Phoenix:
        def pid(self):
            return os.getpid()

        def die(self):
            os._exit(1)

    p = Phoenix.options(max_restarts=2).remote()
    pid1 = ray_tpu.get(p.pid.remote(), timeout=60)
    with pytest.raises(ray_tpu.RayTpuError):
        ray_tpu.get(p.die.remote(), timeout=30)
    deadline = time.time() + 30
    pid2 = None
    while time.time() < deadline:
        try:
            pid2 = ray_tpu.get(p.pid.remote(), timeout=20)
            break
        except ray_tpu.RayTpuError:
            time.sleep(0.5)
    assert pid2 is not None and pid2 != pid1


def test_actor_death_permanent(ray_start_regular):
    @ray_tpu.remote
    class Mortal:
        def die(self):
            os._exit(1)

        def ping(self):
            return "pong"

    m = Mortal.remote()  # max_restarts=0
    with pytest.raises(ray_tpu.RayTpuError):
        ray_tpu.get(m.die.remote(), timeout=30)
    time.sleep(1.5)
    with pytest.raises((ray_tpu.ActorDiedError, ray_tpu.ActorUnavailableError)):
        ray_tpu.get(m.ping.remote(), timeout=20)


def test_actor_creation_failure_surfaces(ray_start_regular):
    @ray_tpu.remote
    class BadInit:
        def __init__(self):
            raise RuntimeError("init-bang")

        def f(self):
            return 1

    b = BadInit.remote()
    with pytest.raises(ray_tpu.RayTpuError):
        ray_tpu.get(b.f.remote(), timeout=60)



CHAOS_SCRIPT = """
import os
os.environ["RAY_TPU_TESTING_RPC_FAILURE"] = (
    "push_task:0.1,push_task_batch:0.1,lease_worker:0.05")
import ray_tpu

ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)

@ray_tpu.remote
def work(i):
    return i * i

# Retries must absorb a 10% injected failure rate on the push path.
vals = ray_tpu.get([work.options(max_retries=20).remote(i)
                    for i in range(100)], timeout=240)
assert vals == [i * i for i in range(100)], vals[:5]

@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def add(self):
        self.n += 1
        return self.n

c = Counter.remote()
out = ray_tpu.get([c.add.remote() for _ in range(50)], timeout=240)
assert out[-1] == 50, out[-5:]
print("CHAOS_OK", flush=True)
ray_tpu.shutdown()
"""


def test_rpc_chaos_injection_absorbed_by_retries():
    """Fault-injected control plane (reference: rpc_chaos.h wired into
    test_gcs_fault_tolerance.py): 10% push failures + 5% lease failures
    must not surface to the application."""
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CHAOS_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert "CHAOS_OK" in out.stdout, out.stdout[-800:] + out.stderr[-2000:]


OOM_SCRIPT = """
import os
os.environ["RAY_TPU_TESTING_MEMORY_USAGE"] = "0.99"
os.environ["RAY_TPU_MEMORY_USAGE_THRESHOLD"] = "0.97"
import time
import ray_tpu

ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)

@ray_tpu.remote
def hold():
    import time
    time.sleep(60)
    return "survived"

# The memory monitor must kill the leased task worker; with retries
# exhausted, the task surfaces WorkerCrashedError.
ref = hold.options(max_retries=0).remote()
try:
    ray_tpu.get(ref, timeout=60)
    print("NO_KILL")
except ray_tpu.WorkerCrashedError:
    print("OOM_KILLED", flush=True)
ray_tpu.shutdown()
"""


def test_memory_monitor_kills_leased_worker():
    """OOM policy (reference: memory_monitor.h + retriable-LIFO killing):
    under (simulated) memory pressure the nodelet kills the most recent
    task worker."""
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", OOM_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "OOM_KILLED" in out.stdout, out.stdout[-500:] + out.stderr[-1500:]


def test_force_cancel_kills_running_task(ray_start_regular):
    """ray.cancel(force=True) stops already-RUNNING work by killing the
    executor (reference: CancelTask force_kill; round-1 cancel was
    pre-execution only)."""
    import time as _t

    @ray_tpu.remote
    def stuck():
        import time

        time.sleep(120)
        return "finished"

    ref = stuck.options(max_retries=0).remote()
    _t.sleep(1.5)  # ensure it is executing
    t0 = _t.time()
    ray_tpu.cancel(ref, force=True)
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=30)
    assert _t.time() - t0 < 20  # did not wait out the 120s sleep
