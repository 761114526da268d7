"""Chaos soak: a real workload (task fan-out with retries, actor calls
across restarts, a serve-style request loop, exactly-once side effects)
completes under seeded delay + failure + partition chaos and worker kills,
inside a bounded wall-clock budget and without the out-of-process
watchdog intervening (ISSUE 5 acceptance)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SOAK_SCRIPT = """
import os, time

os.environ["RAY_TPU_CHAOS_SEED"] = "1301"
# Latency on the lease + push + reply paths, hard failures on the push
# path, a lossy one-way heartbeat ack partition, and failpoint delays on
# the nodelet grant seam — all at once.
os.environ["RAY_TPU_CHAOS_DELAY_MS"] = (
    "*lease_worker=5:60,*push_task*=0:20:0.5,recv.heartbeat=0:20,"
    "nodelet.lease_grant=0:15:0.5")
os.environ["RAY_TPU_TESTING_RPC_FAILURE"] = (
    "push_task:0.05,push_task_batch:0.05,lease_worker:0.03,"
    "nodelet.lease_grant:0.05")
os.environ["RAY_TPU_CHAOS_PARTITION"] = "heartbeat:recv:0.3"
import ray_tpu

t0 = time.time()
ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)

# --- phase 1: fan-out + lineage-style reduce under chaos ---------------
@ray_tpu.remote
def sq(x):
    return x * x

@ray_tpu.remote
def total(xs):
    return sum(xs)

refs = [sq.options(max_retries=20).remote(i) for i in range(150)]
assert ray_tpu.get(total.remote(ray_tpu.get(refs)), timeout=240) == \\
    sum(i * i for i in range(150))
print("PHASE1_OK", flush=True)

# --- phase 2: exactly-once side effects (send-path chaos only touches
# requests BEFORE execution, so retries must not double-execute) --------
import tempfile
d = tempfile.mkdtemp(prefix="chaos_soak_")

@ray_tpu.remote
def mark(i):
    with open(os.path.join(d, str(i)), "a") as f:
        f.write("x")
    return i

assert sorted(ray_tpu.get(
    [mark.options(max_retries=20).remote(i) for i in range(30)],
    timeout=240)) == list(range(30))
dupes = [i for i in range(30)
         if len(open(os.path.join(d, str(i))).read()) != 1]
assert not dupes, f"duplicate side effects: {dupes}"
print("PHASE2_OK", flush=True)

# --- phase 3: actor calls across a worker kill + restart ---------------
@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def add(self):
        self.n += 1
        return self.n
    def die(self):
        os._exit(1)

c = Counter.options(max_restarts=3).remote()
assert ray_tpu.get([c.add.remote() for _ in range(20)],
                   timeout=240)[-1] == 20
try:
    ray_tpu.get(c.die.remote(), timeout=60)
except ray_tpu.RayTpuError:
    pass
deadline = time.time() + 90
recovered = False
while time.time() < deadline:
    try:
        if ray_tpu.get(c.add.remote(), timeout=30) >= 1:
            recovered = True
            break
    except ray_tpu.RayTpuError:
        time.sleep(0.5)
assert recovered, "actor did not recover from kill under chaos"
print("PHASE3_OK", flush=True)

# --- phase 4: serve-style request loop (actor handle hammered from the
# driver while delay chaos reorders pushes/replies) ---------------------
@ray_tpu.remote
class Replica:
    def handle(self, x):
        return x * 2

r = Replica.remote()
for wave in range(10):
    out = ray_tpu.get([r.handle.remote(i) for i in range(32)], timeout=240)
    assert out == [i * 2 for i in range(32)], out
print("PHASE4_OK", flush=True)

elapsed = time.time() - t0
assert elapsed < 420, f"soak exceeded budget: {elapsed:.0f}s"
print(f"SOAK_OK {elapsed:.1f}s", flush=True)
ray_tpu.shutdown()
"""


@pytest.mark.slow
def test_chaos_soak_completes_without_watchdog():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SOAK_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=540)
    assert "SOAK_OK" in out.stdout, \
        out.stdout[-1200:] + out.stderr[-2500:]
