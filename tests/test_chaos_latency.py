"""Deterministic chaos engine: latency injection, one-way partitions,
seeded schedules, and the lease-path hang the delay chaos exposed
(reference: src/ray/common/asio/asio_chaos.cc + rpc_chaos.h)."""

import asyncio
import os
import subprocess
import sys

import pytest

from ray_tpu._private.chaos import ChaosEngine, ChaosInjectedError, set_chaos
from ray_tpu.utils.config import RayTpuConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chaos_reset():
    yield
    set_chaos(None)


def _cfg(**kw):
    # Bypass env overrides: construct the dataclass then force fields.
    cfg = RayTpuConfig()
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


# ---------------------------------------------------------------------------
# Engine-level semantics
# ---------------------------------------------------------------------------
def test_disabled_engine_is_inert():
    e = ChaosEngine(_cfg())
    assert not e.enabled
    assert e.delay_s("anything") == 0.0
    assert not e.should_drop("anything", "send")
    e.maybe_fail("anything")  # no raise
    e.failpoint("anything")  # no raise


def test_delay_bounds_probability_and_patterns():
    e = ChaosEngine(_cfg(chaos_seed=11,
                         chaos_delay_ms="*lease_worker=5:50,push_task=10"))
    # fnmatch pattern covers all three injection points
    for key in ("lease_worker", "server.lease_worker", "recv.lease_worker"):
        vals = [e.delay_s(key) for _ in range(50)]
        assert all(0.005 <= v <= 0.050 for v in vals), (key, vals[:5])
    # single-field entry: fixed delay
    assert e.delay_s("push_task") == pytest.approx(0.010)
    assert e.delay_s("unrelated") == 0.0
    # probability gate fires roughly at the configured rate
    e2 = ChaosEngine(_cfg(chaos_seed=11, chaos_delay_ms="m=10:10:0.3"))
    fired = sum(1 for _ in range(400) if e2.delay_s("m") > 0)
    assert 60 <= fired <= 180, fired


def test_partition_directions_and_peer():
    e = ChaosEngine(_cfg(chaos_seed=3,
                         chaos_partition="heartbeat:recv,echo@gcs:send"))
    assert e.should_drop("heartbeat", "recv", peer="anyone")
    assert not e.should_drop("heartbeat", "send", peer="anyone")
    assert e.should_drop("echo", "send", peer="gcs")
    assert not e.should_drop("echo", "send", peer="nodelet")
    assert not e.should_drop("other", "recv")
    # default direction is both
    e2 = ChaosEngine(_cfg(chaos_partition="x"))
    assert e2.should_drop("x", "send") and e2.should_drop("x", "recv")


def test_failpoint_failure_and_delay():
    e = ChaosEngine(_cfg(chaos_seed=5,
                         testing_rpc_failure="gcs.snapshot_save:1.0",
                         chaos_delay_ms="object_store.spill=1:2"))
    with pytest.raises(ChaosInjectedError):
        e.failpoint("gcs.snapshot_save")
    e.failpoint("object_store.spill")  # delays ~1-2ms, no raise
    assert any(k == "object_store.spill" and a == "delay"
               for k, a, _ in e.schedule)


def test_same_seed_same_schedule_in_process():
    spec = dict(chaos_seed=42,
                chaos_delay_ms="*lease_worker=5:50,push_task=0:20:0.5",
                chaos_partition="heartbeat:recv:0.5",
                testing_rpc_failure="push_task:0.3")

    def drive(e):
        for _ in range(100):
            e.delay_s("lease_worker")
            e.delay_s("server.lease_worker")
            e.delay_s("push_task")
            e.should_drop("heartbeat", "recv", peer="gcs")
            try:
                e.maybe_fail("push_task")
            except ChaosInjectedError:
                pass
        return e.schedule_digest()

    d1 = drive(ChaosEngine(_cfg(**spec)))
    d2 = drive(ChaosEngine(_cfg(**spec)))
    assert d1 == d2
    # interleaving between keys must not perturb any key's stream
    e3 = ChaosEngine(_cfg(**spec))
    for _ in range(100):
        e3.delay_s("push_task")  # different global order...
        e3.delay_s("lease_worker")
        e3.delay_s("server.lease_worker")
        try:
            e3.maybe_fail("push_task")
        except ChaosInjectedError:
            pass
        e3.should_drop("heartbeat", "recv", peer="gcs")
    per_key = sorted(
        (k, a, v) for k, a, v in e3.schedule)
    base = ChaosEngine(_cfg(**spec))
    drive(base)
    assert per_key == sorted((k, a, v) for k, a, v in base.schedule)
    assert drive(ChaosEngine(_cfg(**dict(spec, chaos_seed=43)))) != d1


SEED_SCRIPT = """
import os
os.environ["RAY_TPU_CHAOS_SEED"] = "1234"
os.environ["RAY_TPU_CHAOS_DELAY_MS"] = "*lease_worker=5:50,push_task=0:20:0.5"
os.environ["RAY_TPU_TESTING_RPC_FAILURE"] = "push_task:0.3"
os.environ["RAY_TPU_CHAOS_PARTITION"] = "heartbeat:recv:0.5"
from ray_tpu._private.chaos import ChaosInjectedError, get_chaos

e = get_chaos()
assert e.seed == 1234
for i in range(200):
    e.delay_s("lease_worker")
    e.delay_s("server.lease_worker")
    e.delay_s("push_task")
    e.should_drop("heartbeat", "recv", peer="gcs")
    try:
        e.maybe_fail("push_task")
    except ChaosInjectedError:
        pass
print(e.schedule_digest())
"""


def test_chaos_seed_env_reproduces_schedule_across_runs():
    """Acceptance: RAY_TPU_CHAOS_SEED=<n> reproduces an identical fault
    schedule across two separate runs (processes)."""
    env = dict(os.environ, PYTHONPATH=REPO)
    outs = [
        subprocess.run([sys.executable, "-c", SEED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=120)
        for _ in range(2)
    ]
    for o in outs:
        assert o.returncode == 0, o.stderr[-2000:]
    assert outs[0].stdout == outs[1].stdout
    assert len(outs[0].stdout.strip()) == 64  # a real digest, not empty


# ---------------------------------------------------------------------------
# RPC-plane integration: partitions and the reset-connection regression
# ---------------------------------------------------------------------------
def _run_rpc(coro_factory):
    """Run an async rpc-level scenario on a private loop."""
    return asyncio.run(coro_factory())


def test_rpc_one_way_partition_drops_reply(chaos_reset):
    """recv partition: the server EXECUTES (heartbeat-reaches-GCS model)
    but the caller never sees the ack."""
    from ray_tpu._private.rpc import RpcClient, RpcServer

    set_chaos(ChaosEngine(_cfg(chaos_partition="echo:recv")))
    calls = {"n": 0}

    async def scenario():
        server = RpcServer()

        async def echo(x):
            calls["n"] += 1
            return x

        server.register("echo", echo)
        await server.start()
        client = RpcClient(server.host, server.port, name="srv")
        try:
            with pytest.raises(asyncio.TimeoutError):
                await client.call("echo", x=1, timeout=0.5)
        finally:
            await client.close()
            await server.stop()

    _run_rpc(scenario)
    assert calls["n"] == 1  # request crossed; only the reply vanished


def test_rpc_send_partition_blackholes_request(chaos_reset):
    from ray_tpu._private.rpc import RpcClient, RpcServer

    set_chaos(ChaosEngine(_cfg(chaos_partition="echo:send")))
    calls = {"n": 0}

    async def scenario():
        server = RpcServer()

        async def echo(x):
            calls["n"] += 1
            return x

        server.register("echo", echo)
        await server.start()
        client = RpcClient(server.host, server.port, name="srv")
        try:
            with pytest.raises(asyncio.TimeoutError):
                await client.call("echo", x=1, timeout=0.5)
        finally:
            await client.close()
            await server.stop()

    _run_rpc(scenario)
    assert calls["n"] == 0  # never reached the wire


def test_rpc_delay_reorders_server_dispatch(chaos_reset):
    """Delay chaos on dispatch reorders concurrent handler execution —
    the class of interleaving asio_chaos exists to exercise."""
    from ray_tpu._private.rpc import RpcClient, RpcServer

    set_chaos(ChaosEngine(_cfg(
        chaos_seed=9, chaos_delay_ms="server.first=80:120")))
    order = []

    async def scenario():
        server = RpcServer()

        async def first():
            order.append("first")

        async def second():
            order.append("second")

        server.register("first", first)
        server.register("second", second)
        await server.start()
        client = RpcClient(server.host, server.port, name="srv")
        try:
            f1 = await client.start_call("first")
            f2 = await client.start_call("second")
            await asyncio.wait_for(asyncio.gather(f1, f2), 10)
        finally:
            await client.close()
            await server.stop()

    _run_rpc(scenario)
    assert order == ["second", "first"]  # delayed dispatch lost the race


def test_reset_connection_fails_pending_calls(chaos_reset):
    """Lease-path hang regression (found by delay chaos): one caller's
    timeout resets a SHARED client; every other in-flight call must fail
    fast with ConnectionLost — before the fix they hung for their full
    timeouts (forever for bare start_call futures), so a lease_worker
    sharing the nodelet client with a timed-out call stalled recovery."""
    from ray_tpu._private.rpc import ConnectionLost, RpcClient, RpcServer

    async def scenario():
        server = RpcServer()

        async def slow():
            await asyncio.sleep(30)

        server.register("slow", slow)
        await server.start()
        client = RpcClient(server.host, server.port, name="srv")
        try:
            fut = await client.start_call("slow")  # in-flight, no timeout
            await asyncio.sleep(0.05)
            await client._reset_connection()  # what call_retrying does
            with pytest.raises(ConnectionLost):
                await asyncio.wait_for(fut, 2.0)
        finally:
            await client.close()
            await server.stop()

    _run_rpc(scenario)


# ---------------------------------------------------------------------------
# Cluster-level: the lease + pubsub paths survive seeded delay chaos
# ---------------------------------------------------------------------------
DELAY_CLUSTER_SCRIPT = """
import os
os.environ["RAY_TPU_CHAOS_SEED"] = "7"
os.environ["RAY_TPU_CHAOS_DELAY_MS"] = (
    "*lease_worker=1:40,*push_task*=0:15:0.5,recv.heartbeat=0:30")
import ray_tpu

ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)

@ray_tpu.remote
def sq(x):
    return x * x

@ray_tpu.remote
def total(xs):
    return sum(xs)

# fan-out + a dependent reduce: leases, pushes and replies all delayed
refs = [sq.remote(i) for i in range(32)]
assert ray_tpu.get(total.remote(ray_tpu.get(refs)), timeout=180) == \
    sum(i * i for i in range(32))

@ray_tpu.remote
class Acc:
    def __init__(self):
        self.n = 0
    def add(self, k):
        self.n += k
        return self.n

a = Acc.remote()
out = ray_tpu.get([a.add.remote(1) for _ in range(30)], timeout=180)
assert out[-1] == 30, out[-5:]
print("DELAY_CHAOS_OK", flush=True)
ray_tpu.shutdown()
"""


def test_lease_and_actor_paths_under_seeded_delay_chaos():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", DELAY_CLUSTER_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=420)
    assert "DELAY_CHAOS_OK" in out.stdout, \
        out.stdout[-800:] + out.stderr[-2000:]


HEARTBEAT_PARTITION_SCRIPT = """
import os
os.environ["RAY_TPU_CHAOS_SEED"] = "21"
# Beats reach the GCS; 70% of the acks vanish. The node must stay alive
# (the GCS saw every beat) and work must keep completing.
os.environ["RAY_TPU_CHAOS_PARTITION"] = "heartbeat:recv:0.7"
import time
import ray_tpu

ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)

@ray_tpu.remote
def ping():
    return "ok"

deadline = time.time() + 12  # > heartbeat_failure_threshold * interval
while time.time() < deadline:
    assert ray_tpu.get(ping.remote(), timeout=60) == "ok"
    time.sleep(0.5)

from ray_tpu.util import state
nodes = state.list_nodes()
assert nodes and all(n["alive"] for n in nodes), nodes
print("PARTITION_OK", flush=True)
ray_tpu.shutdown()
"""


def test_one_way_heartbeat_partition_tolerated():
    """Regression for the heartbeat hardening: before bounding the beat's
    RPC timeout to ~2x the interval, a dropped ack stalled the beat loop
    for gcs_rpc_timeout_s (30s) and the GCS declared a healthy node dead."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", HEARTBEAT_PARTITION_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert "PARTITION_OK" in out.stdout, \
        out.stdout[-800:] + out.stderr[-2000:]


HOST_STALL_SCRIPT = """
import os
import signal
import time
os.environ["RAY_TPU_HEARTBEAT_FAILURE_THRESHOLD"] = "3"
import ray_tpu
from ray_tpu import api

ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024 * 1024)

@ray_tpu.remote
class Named:
    def ping(self):
        return "ok"

a = Named.options(name="survivor").remote()
assert ray_tpu.get(a.ping.remote(), timeout=60) == "ok"
# Freeze the GCS and the nodelet together, as a co-located TPU worker's
# runtime start-up freezes the whole host, for longer than
# heartbeat_failure_threshold * interval.
pids = [p.pid for p in api._global_node.processes]
for pid in pids:
    os.kill(pid, signal.SIGSTOP)
time.sleep(4.5)
for pid in pids:
    os.kill(pid, signal.SIGCONT)
time.sleep(2)  # a couple of health-check rounds

from ray_tpu.util import state
nodes = state.list_nodes()
assert nodes and all(n["alive"] for n in nodes), nodes
again = ray_tpu.get_actor("survivor")
assert ray_tpu.get(again.ping.remote(), timeout=60) == "ok"
print("HOST_STALL_OK", flush=True)
ray_tpu.shutdown()
"""


def test_whole_host_stall_does_not_kill_the_node():
    """Found on the TPU host: initializing the TPU runtime in a worker froze
    every process for ~5s; the GCS woke, saw 5s of silence it could not
    have heard through, and declared the node — and every actor on it,
    serve controller included — dead while all of them were running."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", HOST_STALL_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert "HOST_STALL_OK" in out.stdout, \
        out.stdout[-800:] + out.stderr[-2000:]
