"""GCS external-store fault tolerance (reference:
src/ray/gcs/store_client/redis_store_client.h,
gcs_redis_failure_detector.h; test strategy from
python/ray/tests/test_gcs_fault_tolerance.py).

The GCS persists row-wise to sqlite (core/store_client.py). These tests
SIGKILL the GCS mid-workload — with RPC chaos injected — restart it on the
same store, and require: named actors resolvable and stateful, placement
groups still usable, and a get that was in flight across the outage to
complete."""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


CHAOS_FT_SCRIPT = """
import os, threading, time
os.environ["RAY_TPU_TESTING_RPC_FAILURE"] = "push_task:0.05,lease_worker:0.02"
import ray_tpu
from ray_tpu import cluster_utils

cluster = cluster_utils.Cluster(initialize_head=True,
                                head_node_args=dict(num_cpus=4,
                                object_store_memory=128 * 1024 * 1024))
ray_tpu.init(address=cluster.address)

store = os.path.join(cluster.head_node.session_dir, "gcs_store.sqlite")
assert os.path.exists(store), f"sqlite store missing: {store}"

@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def bump(self):
        self.n += 1
        return self.n
    def slow(self):
        time.sleep(4.0)
        self.n += 1
        return self.n

c = Counter.options(name="chaos-survivor").remote()
assert ray_tpu.get(c.bump.remote(), timeout=60) == 1

# placement group committed before the outage
from ray_tpu.util import placement_group
pg = placement_group([{"CPU": 1}], strategy="PACK")
assert pg.ready(timeout=60)

time.sleep(0.6)  # debounced store flush

# a get that stays in flight ACROSS the GCS outage
slow_ref = c.slow.remote()
result = {}
def waiter():
    result["v"] = ray_tpu.get(slow_ref, timeout=120)
t = threading.Thread(target=waiter)
t.start()

cluster.head_node.restart_gcs()          # SIGKILL + restart on same store
time.sleep(2.0)                          # nodes re-register via heartbeat

t.join(timeout=120)
assert result.get("v") == 2, result

# named actor survived with state (resolved through the NEW GCS)
c2 = ray_tpu.get_actor("chaos-survivor")
assert ray_tpu.get(c2.bump.remote(), timeout=60) == 3

# the committed placement group still schedules work
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

@ray_tpu.remote
def in_pg():
    return "ok"

assert ray_tpu.get(
    in_pg.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=0)).remote(),
    timeout=120) == "ok"

# fresh work under continuing chaos
vals = ray_tpu.get([in_pg.options(max_retries=20).remote()
                    for _ in range(20)], timeout=120)
assert vals == ["ok"] * 20
print("GCS_FT_OK", flush=True)
ray_tpu.shutdown()
"""


def test_gcs_sqlite_store_survives_kill_under_chaos():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CHAOS_FT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert "GCS_FT_OK" in out.stdout, \
        out.stdout[-800:] + out.stderr[-2000:]


LOG_CURSOR_SCRIPT = """
import time
import ray_tpu
from ray_tpu import cluster_utils
from ray_tpu._private import worker as worker_mod

cluster = cluster_utils.Cluster(initialize_head=True,
                                head_node_args=dict(num_cpus=2,
                                object_store_memory=128 * 1024 * 1024))
ray_tpu.init(address=cluster.address)      # log_to_driver is the default
w = worker_mod.global_worker()

@ray_tpu.remote
def say(line):
    print(line, flush=True)
    return 1

# Raise the old GCS's 'logs' sequence well past what the new one will
# reach: the nodelet publishes one batch a tail (0.5 s), so one print a
# round and a wait.
deadline = time.monotonic() + 60
n = 0
while w._pubsub_cursors.get("logs", 0) < 8:
    assert time.monotonic() < deadline, w._pubsub_cursors
    ray_tpu.get(say.remote("BEFORE-%d" % n), timeout=60)
    n += 1
    time.sleep(0.7)
old = w._pubsub_cursors["logs"]

cluster.head_node.restart_gcs()
time.sleep(2.0)                            # nodes re-register via heartbeat
assert ray_tpu.get(say.remote("AFTER-RESTART-marker"), timeout=60) == 1
time.sleep(4.0)                            # tail, publish, poll, print

new = w._pubsub_cursors["logs"]
try:       # the GCS holds a poll for 30 s; the call gives up after 1.5
    out = w.loop_thread.run(w.gcs_client.call(
        "pubsub_poll", cursors={"logs": new}, timeout=1.5), timeout=30)
except TimeoutError:
    out = None
print("CURSOR old=%d new=%d poll=%s"
      % (old, new, "blocks" if out is None else "answers"), flush=True)
ray_tpu.shutdown()
cluster.shutdown()
"""


def test_log_subscription_counts_with_the_restarted_gcs():
    """A restarted GCS counts its channels from 1 again. The driver's log
    subscription must take up the new count: a line printed after the
    restart reaches the driver's stderr once (with the old, higher cursor
    kept, every poll was answered at once with the whole new backlog, and
    the driver printed it again and again), and a poll from the driver's
    cursor then blocks."""
    import re

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", LOG_CURSOR_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=200)
    tail = out.stdout[-800:] + out.stderr[-2000:]
    m = re.search(r"CURSOR old=(\d+) new=(\d+) poll=(\w+)", out.stdout)
    assert m, tail
    assert out.stderr.count("AFTER-RESTART-marker") == 1, tail
    assert 0 < int(m[2]) < int(m[1]), tail  # it counts with the new GCS
    assert m[3] == "blocks", tail


def test_sqlite_store_incremental_and_roundtrip(tmp_path):
    from ray_tpu.core.store_client import (
        FileStoreClient,
        SqliteStoreClient,
        create_store_client,
    )

    path = str(tmp_path / "gcs.sqlite")
    s = create_store_client(path)
    assert isinstance(s, SqliteStoreClient)
    tables = {"kv": {"a": b"1", "b": b"2"},
              "actors": {"x": {"state": "ALIVE"}},
              "job_counter": 7}
    s.save(tables)
    # unchanged save writes nothing (digest cache) — observe via mtime of
    # the WAL-journaled db staying stable across a no-op save
    s.save(tables)
    s.close()

    s2 = create_store_client(path)
    loaded = s2.load()
    assert loaded["kv"] == {"a": b"1", "b": b"2"}
    assert loaded["actors"]["x"]["state"] == "ALIVE"
    assert loaded["job_counter"] == 7
    # deletion tracked
    del tables["kv"]["b"]
    s2.save(tables)
    s2.close()
    s3 = create_store_client(path)
    assert s3.load()["kv"] == {"a": b"1"}
    s3.close()

    f = create_store_client(str(tmp_path / "gcs.pkl"))
    assert isinstance(f, FileStoreClient)
    f.save(tables)
    assert f.load()["job_counter"] == 7
