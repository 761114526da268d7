"""Op-DAG streaming executor: bounded-memory scheduling, actor-pool
autoscaling, streaming_split epochs, and the store-byte budget contract
(reference: python/ray/data/_internal/execution/streaming_executor.py +
autoscaler/default_autoscaler.py).
"""

import os
import time

import numpy as np
import pytest

from ray_tpu.data._execution.autoscaler import PoolAutoscalerPolicy
from ray_tpu.data.planner import ExecutionBudget, ResourceManager


# ---------------------------------------------------------------------------
# Pure-policy units (no cluster)
# ---------------------------------------------------------------------------
class TestPoolAutoscalerPolicy:
    CFG = {"up_delay_s": 0.1, "down_delay_s": 0.1,
           "up_cooldown_s": 0.1, "down_cooldown_s": 0.1}

    def test_scale_up_needs_sustained_pressure(self):
        p = PoolAutoscalerPolicy(1, 4, self.CFG)
        # Instantaneous spike: no decision before the delay window.
        assert p.tick(0.0, queued=8, pool_size=1, idle=0) == 0
        assert p.tick(0.05, queued=8, pool_size=1, idle=0) == 0
        assert p.tick(0.11, queued=8, pool_size=1, idle=0) == 1

    def test_pressure_blip_resets_hysteresis(self):
        p = PoolAutoscalerPolicy(1, 4, self.CFG)
        assert p.tick(0.0, queued=8, pool_size=1, idle=0) == 0
        # Queue drains mid-window: the up timer must restart.
        assert p.tick(0.05, queued=0, pool_size=1, idle=0) == 0
        assert p.tick(0.06, queued=8, pool_size=1, idle=0) == 0
        assert p.tick(0.12, queued=8, pool_size=1, idle=0) == 0
        assert p.tick(0.17, queued=8, pool_size=1, idle=0) == 1

    def test_cooldown_blocks_double_fire(self):
        p = PoolAutoscalerPolicy(1, 4, self.CFG)
        p.tick(0.0, queued=8, pool_size=1, idle=0)
        assert p.tick(0.11, queued=8, pool_size=1, idle=0) == 1
        # Within cooldown: silent even under sustained pressure.
        assert p.tick(0.15, queued=8, pool_size=2, idle=0) == 0
        assert p.tick(0.22, queued=8, pool_size=2, idle=0) == 0
        assert p.tick(0.33, queued=8, pool_size=2, idle=0) == 1

    def test_scale_down_is_idle_limited(self):
        p = PoolAutoscalerPolicy(1, 4, dict(self.CFG, max_step=4))
        p.tick(0.0, queued=0, pool_size=4, idle=1)
        # Only 1 idle: never shrink past what is provably drained,
        # even with max_step=4 and 3 actors above the floor.
        assert p.tick(0.11, queued=0, pool_size=4, idle=1) == -1

    def test_never_exceeds_bounds(self):
        p = PoolAutoscalerPolicy(2, 3, self.CFG)
        p.tick(0.0, queued=50, pool_size=3, idle=0)
        assert p.tick(0.2, queued=50, pool_size=3, idle=0) == 0  # at max
        p2 = PoolAutoscalerPolicy(2, 3, self.CFG)
        p2.tick(0.0, queued=0, pool_size=2, idle=2)
        assert p2.tick(0.2, queued=0, pool_size=2, idle=2) == 0  # at min


class TestStoreBytesContract:
    """ExecutionBudget.store_bytes caps resident bytes; the bound is
    shrink-only against the reservation window."""

    def test_headroom_accounting(self):
        rm = ResourceManager(ExecutionBudget(cpu_slots=8, store_bytes=100))
        assert rm.store_headroom() == 100
        rm.on_bytes_acquired(70)
        assert rm.store_headroom() == 30
        # Sizes are only known after blocks exist: overshoot is legal
        # and must clamp headroom, not crash.
        rm.on_bytes_acquired(70)
        assert rm.store_headroom() == -40
        assert rm.peak_held_bytes == 140
        rm.on_bytes_released(140)
        assert rm.store_headroom() == 100
        # Release never goes negative.
        rm.on_bytes_released(10**9)
        assert rm.held_bytes == 0

    def test_shrink_only_under_pressure(self):
        class Op:
            name = "op"
            num_cpus = 1.0
            window = 8

        op = Op()
        rm = ResourceManager(ExecutionBudget(cpu_slots=8, store_bytes=100))
        rm.register_ops([op])
        unpressured = rm.max_inflight(op)
        assert unpressured >= 1
        rm.on_bytes_acquired(100)
        # Budget exhausted: drain mode, but never below 1 — forward
        # progress is what releases bytes.
        assert rm.max_inflight(op) == 1
        rm.on_bytes_released(50)
        # Recovery never exceeds the reservation bound (shrink-only).
        assert rm.max_inflight(op) <= unpressured

    def test_no_budget_means_no_byte_bound(self):
        rm = ResourceManager(ExecutionBudget(cpu_slots=8, store_bytes=None))
        rm.on_bytes_acquired(10**12)
        assert rm.store_headroom() is None

    def test_env_override_parses(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_DATA_STORE_BYTES", "12345")
        assert ExecutionBudget.default().store_bytes == 12345
        monkeypatch.setenv("RAY_TPU_DATA_STORE_BYTES", "banana")
        ExecutionBudget.default()  # bad value: warn, never raise


def test_concurrency_tuple_validation():
    import ray_tpu.data as rd

    ds = rd.range(10)
    with pytest.raises(ValueError, match="callable class"):
        ds.map_batches(lambda b: b, concurrency=(1, 2))
    with pytest.raises(ValueError, match="min <= max"):
        ds.map_batches(type("C", (), {"__call__": lambda s, b: b}),
                       concurrency=(3, 2))

    class F:
        def __call__(self, b):
            return b

    out = ds.map_batches(F, concurrency=(1, 3))
    op = out._plan[-1]
    assert op.concurrency == 1 and op.max_concurrency == 3


# ---------------------------------------------------------------------------
# Cluster tests. All transform fns/classes are locals: cloudpickle ships
# them by value — a module-level def would make workers try (and fail)
# to import this test module.
# ---------------------------------------------------------------------------
def _double():
    return lambda b: {"id": b["id"] * 2}


def _three_stage_plan(n_rows=4000, block_rows=250):
    """source → task map → actor map; task/actor stages never fuse, so
    the executor runs ≥ 2 distinct map operators."""
    import ray_tpu.data as rd

    class AddTag:
        def __call__(self, b):
            return {"id": b["id"] + 1}

    return (rd.range(n_rows, block_rows=block_rows)
            .map_batches(_double(), batch_size=block_rows)
            .map_batches(AddTag, batch_size=block_rows, concurrency=2))


def test_three_stage_bounded_memory_slow_sink(ray_cluster):
    """The acceptance pipeline: a deliberately slow sink consumer, a
    store budget of a few blocks — peak resident bytes stay bounded
    while ≥ 2 operators hold concurrent in-flight work, and every
    operator's throughput lands in the telemetry breakdown."""
    from ray_tpu.data._execution import StreamingExecutor

    ds = _three_stage_plan()
    block_bytes = 250 * 8  # int64 column, 250 rows per block
    budget = ExecutionBudget(store_bytes=4 * block_bytes)
    ex = StreamingExecutor(ds._plan, budget=budget)
    rows = 0
    try:
        while True:
            try:
                ref = ex.next_output()
            except StopIteration:
                break
            block = ray_cluster.get(ref)
            rows += len(block["id"])
            time.sleep(0.01)  # the slow sink
    finally:
        ex.shutdown()
    assert rows == 4000
    summary = ex.summary()
    # Peak resident bytes bounded by the budget. Overshoot of one block
    # per launched-before-pressure operator is inherent (sizes are known
    # only once a block exists); anything beyond that means the gate
    # never engaged.
    assert summary["peak_held_bytes"] <= budget.store_bytes + 3 * block_bytes
    # Upstream stayed busy while the sink dawdled: concurrent in-flight
    # across at least the task stage and the actor stage.
    assert summary["max_concurrent_ops"] >= 2
    # Per-operator throughput visible in the breakdown.
    map_rows = [op["rows_out"] for op in summary["ops"]]
    assert all(r == 4000 for r in map_rows), summary["ops"]
    from ray_tpu.util.metrics import get_counter

    snap = get_counter("ray_tpu_data_op_output_rows_total").snapshot()
    assert sum(snap["values"].values()) > 0


def test_output_order_is_input_order(ray_cluster):
    import ray_tpu.data as rd

    vals = (rd.range(2000, block_rows=100)
            .map_batches(_double(), batch_size=100)
            .map_batches(lambda b: {"id": -b["id"]}, batch_size=100,
                         num_cpus=0.5)
            .take_all())
    assert [r["id"] for r in vals] == [-2 * i for i in range(2000)]


def test_budget_smaller_than_one_block_completes(ray_cluster):
    """A budget below a single block's size must degrade to serial
    drain execution, never deadlock."""
    from ray_tpu.data._execution import StreamingExecutor

    ds = _three_stage_plan(n_rows=1000, block_rows=200)
    ex = StreamingExecutor(ds._plan, budget=ExecutionBudget(store_bytes=1))
    rows = 0
    try:
        while True:
            try:
                rows += len(ray_cluster.get(ex.next_output())["id"])
            except StopIteration:
                break
    finally:
        ex.shutdown()
    assert rows == 1000


def test_actor_pool_autoscales_up_then_drains(ray_cluster):
    """Sustained input-queue depth grows the pool; an empty queue drains
    it back down — both transitions observable in the summary."""
    import ray_tpu.data as rd
    from ray_tpu.data._execution import StreamingExecutor
    from ray_tpu.data._execution.operators import ActorPoolMapOperator

    class SlowWorker:
        def __call__(self, b):
            import time as _t
            _t.sleep(0.03)
            return b

    ds = (rd.range(6000, block_rows=100)
          .map_batches(SlowWorker, batch_size=100, concurrency=(1, 3)))
    op = ds._plan[-1]
    # Tight windows so the test observes both transitions quickly.
    op.autoscale_config = {"up_delay_s": 0.05, "down_delay_s": 0.05,
                           "up_cooldown_s": 0.05, "down_cooldown_s": 0.05}
    ex = StreamingExecutor(ds._plan)
    pool_op = next(o for o in ex.ops
                   if isinstance(o, ActorPoolMapOperator))
    rows = 0
    try:
        while True:
            try:
                ref = ex.next_output()
            except StopIteration:
                break
            rows += len(ray_cluster.get(ref)["id"])
            # Slow-ish sink keeps the executor ticking through the
            # drain phase so scale-down is observable too.
            time.sleep(0.005)
        deadline = time.monotonic() + 10
        # Input exhausted; keep ticking until the pool drains back.
        while (pool_op.pool_size() > 1
               and time.monotonic() < deadline):
            ex._tick()
            time.sleep(0.01)
    finally:
        ex.shutdown()
    assert rows == 6000
    assert pool_op.pool_size_peak >= 2, "pool never scaled up"
    assert pool_op.scale_ups >= 1
    assert pool_op.scale_downs >= 1, "pool never drained back down"
    summary = ex.summary()
    assert summary["autoscale_events"] >= 2


def test_streaming_split_uneven_consumers_no_loss(ray_cluster):
    """One split consumer runs far ahead; the laggard must still get
    every one of its blocks — no deadlock, no drops."""
    import ray_tpu.data as rd

    ds = rd.range(800, block_rows=50).map_batches(_double(),
                                                  batch_size=50)
    its = ds.streaming_split(2)
    # Consumer 0 drains its entire stream first.
    fast = [r["id"] for r in its[0].iter_rows()]
    # Only then does consumer 1 start.
    slow = [r["id"] for r in its[1].iter_rows()]
    assert sorted(fast + slow) == [2 * i for i in range(800)]
    assert fast and slow, "round-robin must feed both splits"


def test_streaming_split_epochs_reset(ray_cluster):
    import ray_tpu.data as rd

    ds = rd.range(400, block_rows=50).map_batches(_double(),
                                                  batch_size=50)
    its = ds.streaming_split(2)
    for _epoch in range(2):
        a = [r["id"] for r in its[0].iter_rows()]
        b = [r["id"] for r in its[1].iter_rows()]
        assert sorted(a + b) == [2 * i for i in range(400)]
        its[0].new_epoch()


def test_execution_summaries_exposed(ray_cluster):
    import ray_tpu.data as rd

    rd.range(200, block_rows=50).map_batches(
        _double(), batch_size=50).take_all()
    summaries = rd.execution_summaries()
    assert summaries, "finished executions must be recorded"
    last = summaries[-1]
    assert {"dataset", "ops", "max_concurrent_ops",
            "peak_held_bytes"} <= set(last)
    assert any(op["rows_out"] == 200 for op in last["ops"])


@pytest.mark.slow
def test_bounded_memory_autoscale_soak(ray_cluster):
    """Chaos-shard soak: a long three-stage run with a small budget and
    an autoscaling pool — resident bytes stay bounded for the whole run
    and every row arrives exactly once."""
    import ray_tpu.data as rd
    from ray_tpu.data._execution import StreamingExecutor

    class Jitter:
        def __call__(self, b):
            import time as _t

            import numpy as _np
            _t.sleep(0.002 + 0.004 * float(_np.random.rand()))
            return {"id": b["id"] + 1}

    n, rows_per = 40000, 500
    ds = (rd.range(n, block_rows=rows_per)
          .map_batches(_double(), batch_size=rows_per)
          .map_batches(Jitter, batch_size=rows_per, concurrency=(1, 4)))
    block_bytes = rows_per * 8
    budget = ExecutionBudget(store_bytes=6 * block_bytes)
    ex = StreamingExecutor(ds._plan, budget=budget)
    total, peak_ok = 0, True
    try:
        while True:
            try:
                ref = ex.next_output()
            except StopIteration:
                break
            total += len(ray_cluster.get(ref)["id"])
            if ex._rm.held_bytes > budget.store_bytes + 4 * block_bytes:
                peak_ok = False
    finally:
        ex.shutdown()
    assert total == n
    assert peak_ok, "resident bytes escaped the budget mid-run"
    summary = ex.summary()
    assert summary["max_concurrent_ops"] >= 2
    assert summary["peak_held_bytes"] <= budget.store_bytes + 4 * block_bytes
