"""util components: ActorPool, Queue, CLI (reference: ray.util)."""

import json
import os
import subprocess
import sys

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.queue import Empty, Queue

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_actor_pool_ordered_and_unordered(ray_start_regular):
    @ray_tpu.remote
    class Sq:
        def sq(self, x):
            return x * x

    pool = ActorPool([Sq.remote() for _ in range(2)])
    out = list(pool.map(lambda a, v: a.sq.remote(v), range(8)))
    assert out == [i * i for i in range(8)]
    out2 = sorted(pool.map_unordered(lambda a, v: a.sq.remote(v), range(8)))
    assert out2 == sorted(i * i for i in range(8))


def test_distributed_queue(ray_start_regular):
    q = Queue(maxsize=4)
    for i in range(4):
        q.put(i)
    assert q.qsize() == 4

    @ray_tpu.remote
    def consume(q, n):
        return [q.get(timeout=30) for _ in range(n)]

    got = ray_tpu.get(consume.remote(q, 4), timeout=60)
    assert got == [0, 1, 2, 3]
    assert q.empty()
    try:
        q.get_nowait()
        assert False, "expected Empty"
    except Empty:
        pass


def test_cli_status(ray_start_regular):
    from ray_tpu._private import worker as wm

    addr = "%s:%d" % wm.global_worker().gcs_address
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "--address", addr,
         "status"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]
    summary = json.loads(out.stdout)
    assert summary["nodes_alive"] >= 1


def test_user_metrics_counter_gauge_histogram(ray_start_regular):
    """ray_tpu.util.metrics: per-process metrics merge cluster-wide through
    the GCS (reference: ray.util.metrics -> metrics agent -> Prometheus)."""
    from ray_tpu.util import metrics

    c = metrics.Counter("test_requests", tag_keys=("route",))
    g = metrics.Gauge("test_queue_depth")
    h = metrics.Histogram("test_latency", boundaries=(0.1, 1.0))
    for _ in range(5):
        c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/b"})
    g.set(7.0)
    h.observe(0.05)
    h.observe(0.5)
    h.observe(3.0)
    metrics.flush()

    # A remote worker contributes to the same counter.
    @ray_tpu.remote
    def bump():
        from ray_tpu.util import metrics as m

        cc = m.Counter("test_requests", tag_keys=("route",))
        cc.inc(10.0, tags={"route": "/a"})
        m.flush()
        return True

    assert ray_tpu.get(bump.remote(), timeout=60)

    merged = metrics.query_metrics()
    reqs = merged["test_requests"]["values"]
    assert reqs[(("route", "/a"),)] == 15.0
    assert reqs[(("route", "/b"),)] == 2.0
    assert merged["test_queue_depth"]["values"][()] == 7.0
    hist = merged["test_latency"]["values"][()]
    assert hist["count"] == 3 and hist["counts"] == [1, 1, 1]


def test_memory_resource_schedules(ray_start_regular):
    """`memory=` is a schedulable resource (reference: ray memory-aware
    scheduling — admission control; OOM policy enforces)."""
    import ray_tpu

    total = ray_tpu.cluster_resources().get("memory", 0)
    assert total > 0  # advertised from /proc/meminfo

    @ray_tpu.remote
    def uses_memory():
        return 1

    # Fits: schedules normally.
    ref = uses_memory.options(memory=64 * 1024 * 1024).remote()
    assert ray_tpu.get(ref, timeout=60) == 1


def test_memory_summary_state(ray_start_regular):
    from ray_tpu.util import state

    ref = __import__("ray_tpu").put(b"x" * 2048)
    mem = state.memory_summary()
    assert mem["stores"] and "bytes_in_use" in mem["stores"][0]
    assert mem["this_process_refs"]["owned"] >= 1
    del ref
