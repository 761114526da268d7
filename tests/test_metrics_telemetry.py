"""Runtime telemetry: Prometheus exposition, cross-process merge, the
dashboard metrics contract on a live cluster, the task lifecycle
breakdown, and stitched runtime traces (reference: src/ray/stats/ +
GcsTaskManager state timeline + tracing_helper.py)."""

import time

import ray_tpu
from ray_tpu.util import metrics as um
from ray_tpu.util import state, tracing


# ---------------------------------------------------------------------------
# Pure exposition / merge units (no cluster).
# ---------------------------------------------------------------------------
def test_render_prometheus_escapes_labels():
    merged = {
        "reqs_total": {
            "kind": "counter",
            "description": "requests",
            "values": {(("route", 'a"b\\c\nd'),): 3.0},
        }
    }
    text = um.render_prometheus(merged)
    assert "# HELP reqs_total requests" in text
    assert "# TYPE reqs_total counter" in text
    # backslash, quote, and newline all escaped — one bad tag must not
    # invalidate the scrape body
    assert 'reqs_total{route="a\\"b\\\\c\\nd"} 3.0' in text


def test_render_prometheus_histogram_series():
    merged = {
        "lat": {
            "kind": "histogram",
            "description": "",
            "values": {
                (): {"boundaries": (0.1, 1.0), "counts": [2, 1, 1],
                     "sum": 2.5, "count": 4},
            },
        }
    }
    lines = um.render_prometheus(merged).splitlines()
    assert "# TYPE lat histogram" in lines
    # buckets are CUMULATIVE and capped by +Inf
    assert 'lat_bucket{le="0.1"} 2' in lines
    assert 'lat_bucket{le="1.0"} 3' in lines
    assert 'lat_bucket{le="+Inf"} 4' in lines
    assert "lat_sum 2.5" in lines
    assert "lat_count 4" in lines


def test_merge_snapshots_cross_process():
    merged, freshest = {}, {}
    um.merge_snapshot(merged, freshest, [
        {"name": "c", "kind": "counter", "description": "",
         "values": {(): 2.0}, "ts": 1.0},
        {"name": "g", "kind": "gauge", "description": "",
         "values": {(): 5.0}, "ts": 1.0},
        {"name": "h", "kind": "histogram", "description": "",
         "values": {(): {"boundaries": (1.0,), "counts": [1, 0],
                         "sum": 0.5, "count": 1}}, "ts": 1.0},
    ])
    um.merge_snapshot(merged, freshest, [
        {"name": "c", "kind": "counter", "description": "",
         "values": {(): 3.0}, "ts": 2.0},
        {"name": "g", "kind": "gauge", "description": "",
         "values": {(): 7.0}, "ts": 2.0},
        {"name": "h", "kind": "histogram", "description": "",
         "values": {(): {"boundaries": (1.0,), "counts": [0, 2],
                         "sum": 4.0, "count": 2}}, "ts": 2.0},
    ])
    assert merged["c"]["values"][()] == 5.0  # counters sum
    assert merged["g"]["values"][()] == 7.0  # gauges keep freshest
    h = merged["h"]["values"][()]
    assert h["counts"] == [1, 2] and h["count"] == 3 and h["sum"] == 4.5
    # A LATE-ARRIVING but OLDER gauge snapshot must not win.
    um.merge_snapshot(merged, freshest, [
        {"name": "g", "kind": "gauge", "description": "",
         "values": {(): 1.0}, "ts": 0.5},
    ])
    assert merged["g"]["values"][()] == 7.0


def test_contract_checker_flags_orphans(tmp_path, monkeypatch):
    from ray_tpu.scripts import check_metrics_contract as cmc

    # The real dashboards must pass against the real tree.
    assert cmc.main() == 0
    # And a dashboard promising a nonexistent metric must fail.
    dash = tmp_path / "dash"
    dash.mkdir()
    (dash / "x.json").write_text(
        '{"panels": [{"targets": [{"expr": '
        '"rate(ray_tpu_this_is_never_emitted_total[1m])"}]}]}')
    monkeypatch.setattr(cmc, "DASHBOARD_DIR", str(dash))
    assert cmc.main() == 1


# ---------------------------------------------------------------------------
# Live-cluster telemetry.
# ---------------------------------------------------------------------------
def fr_sample_every() -> int:
    from ray_tpu._private import flight_recorder as fr

    return fr._SAMPLE_EVERY


def test_dashboard_promised_metrics_live(ray_start_regular):
    """Acceptance: every metric name the shipped Grafana dashboards
    reference appears in the /metrics text exposition of a live cluster
    (prometheus_text() is exactly the body the dashboard route serves)."""
    from ray_tpu import serve
    from ray_tpu.collective import collective as col
    from ray_tpu.scripts.check_metrics_contract import dashboard_metric_names

    @ray_tpu.remote
    def tele_live(x):
        return x + 1

    assert ray_tpu.get([tele_live.remote(i) for i in range(4)]) == [1, 2, 3, 4]

    @serve.deployment
    def tele_echo(request):
        return {"ok": True}

    try:
        handle = serve.run(tele_echo.bind())
        assert handle.remote({"body": {}}).result(timeout=60) == {"ok": True}

        @ray_tpu.remote
        class Rank:
            def __init__(self, rank, n):
                self.group = col.init_collective_group(
                    n, rank, group_name="tele_mtr")

            def run(self):
                import numpy as np

                return float(self.group.allreduce_host(np.ones(2))[0])

        members = [Rank.remote(i, 2) for i in range(2)]
        assert ray_tpu.get([m.run.remote() for m in members],
                           timeout=60) == [2.0, 2.0]

        # The data panels: a small pipeline through a task stage and an
        # actor pool (cloudpickle ships the local class by value).
        import numpy as np

        import ray_tpu.data as rd

        class AddOne:
            def __call__(self, b):
                return {"id": b["id"] + 1}

        ds = (rd.range(400, block_rows=50)
              .map_batches(lambda b: {"id": b["id"] * 2})
              .map_batches(AddOne, concurrency=(1, 2)))
        assert sorted(r["id"] for r in ds.take_all()) == \
            [2 * i + 1 for i in range(400)]

        # The object-store phase panels: a put of 1 MiB or more is always
        # timed, gets are sampled 1-in-RAY_TPU_FR_SAMPLE.
        ref = ray_tpu.put(np.ones(2 << 20, np.uint8))
        for _ in range(4 * fr_sample_every()):
            assert ray_tpu.get(ref)[-1] == 1

        um.flush()  # the driver's own registry, without the 2s wait
        names = set(dashboard_metric_names())
        assert names, "no promised names found — dashboards moved?"
        deadline = time.time() + 45
        missing = names
        while time.time() < deadline:
            text = um.prometheus_text()
            missing = {n for n in names if n not in text}
            if not missing:
                break
            time.sleep(1.0)
        assert not missing, \
            f"dashboard metrics absent from /metrics: {sorted(missing)}"
    finally:
        serve.shutdown()


def test_task_latency_breakdown_sums_to_e2e(ray_start_regular):
    """Acceptance: queue+lease+fetch+exec telescopes to the end-to-end
    duration (every stamp sits on the same host wall clock)."""

    @ray_tpu.remote
    def tele_sleep(x):
        time.sleep(0.02)
        return x

    ray_tpu.get([tele_sleep.remote(i) for i in range(8)])
    row = None
    deadline = time.time() + 25
    while time.time() < deadline:
        row = state.task_latency_breakdown().get("tele_sleep")
        if (row and row.get("e2e", {}).get("count", 0) >= 8
                and all(p in row for p in ("queue", "lease", "fetch",
                                           "exec"))):
            break
        time.sleep(0.5)
    assert row, "breakdown never materialized from task events"
    for phase in ("queue", "lease", "fetch", "exec", "e2e"):
        assert row[phase]["count"] >= 8, (phase, row)
        assert row[phase]["p50"] <= row[phase]["p95"] <= row[phase]["max"]
    phase_sum = sum(row[p]["mean"]
                    for p in ("queue", "lease", "fetch", "exec"))
    e2e = row["e2e"]["mean"]
    assert abs(phase_sum - e2e) <= max(0.02, 0.1 * e2e), (phase_sum, e2e)
    # the deliberate sleep lands in exec, not in the runtime phases
    assert row["exec"]["p50"] >= 0.015


def test_cli_tasks_breakdown_prints(ray_start_regular):
    import json
    import os
    import subprocess
    import sys

    from ray_tpu import api as api_mod

    @ray_tpu.remote
    def tele_cli(x):
        return x

    ray_tpu.get([tele_cli.remote(i) for i in range(3)])
    time.sleep(2.0)  # executor event flush cadence is 1s
    node = api_mod._global_node
    addr = f"{node.gcs_address[0]}:{node.gcs_address[1]}"
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "tasks",
         "--breakdown", "--address", addr],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    breakdown = json.loads(out.stdout)
    assert isinstance(breakdown, dict) and breakdown
    some_fn = next(iter(breakdown.values()))
    assert "exec" in some_fn and "p50" in some_fn["exec"]


def test_driver_span_parents_runtime_spans(ray_start_regular):
    """Acceptance: a driver-side span around .remote() yields ONE connected
    trace — task row parented to the driver span, phase spans (lease/
    fetch/exec) parented to the task row."""

    @ray_tpu.remote
    def traced_fn():
        return 1

    with tracing.span("driver-step") as root:
        assert ray_tpu.get(traced_fn.remote()) == 1

    task_row, phases = None, []
    deadline = time.time() + 25
    while time.time() < deadline:
        events = state.timeline()
        tasks = [e for e in events if e["name"] == "traced_fn"
                 and e["args"].get("parent") == root]
        if tasks:
            tid = tasks[0]["args"]["task_id"]
            phases = [e for e in events if e["name"].startswith("phase:")
                      and e["args"].get("parent") == tid]
            if {p["name"] for p in phases} >= {"phase:queue", "phase:lease",
                                               "phase:fetch", "phase:exec"}:
                task_row = tasks[0]
                break
        time.sleep(0.5)
    assert task_row is not None, "task row never parented under driver span"
    by_name = {p["name"]: p for p in phases}
    # phases tile the task's lifetime in breakdown order
    assert (by_name["phase:queue"]["ts"]
            <= by_name["phase:lease"]["ts"]
            <= by_name["phase:fetch"]["ts"]
            <= by_name["phase:exec"]["ts"])


def test_timeline_tolerates_malformed_events(ray_start_regular):
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker()
    w.record_event({"task_id": "telemetry-bad-1", "type": "TEST"})
    w.record_event({"task_id": "telemetry-bad-2", "name": "x",
                    "start_ts": time.time()})
    deadline = time.time() + 15
    while time.time() < deadline:
        if any(e.get("task_id") == "telemetry-bad-1"
               for e in state.list_tasks(limit=20_000)):
            break
        time.sleep(0.25)
    events = state.timeline()  # must skip the malformed rows, not raise
    assert isinstance(events, list)
    assert not any(e["args"].get("task_id") == "telemetry-bad-1"
                   for e in events)


def test_task_event_buffer_bounded(ray_start_regular, monkeypatch):
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker()
    monkeypatch.setattr(worker_mod, "_TASK_EVENT_BUFFER_MAX", 25)
    counter = um.get_counter("ray_tpu_task_events_dropped_total")
    before = counter._values.get((), 0.0)
    now = time.time()
    for i in range(200):
        w.record_event({"task_id": f"telemetry-bound-{i}", "name": "bounded",
                        "type": "TEST", "start_ts": now, "end_ts": now,
                        "ok": True})
    with w._task_events_lock:
        buffered = len(w._task_events)
    assert buffered <= 25  # oldest-first eviction, never unbounded
    assert counter._values.get((), 0.0) > before  # drops are counted


def test_serve_shed_metric_emitted(ray_start_regular):
    """Overload sheds are COUNTED: a replica-capacity shed shows up in
    the cross-process merged ray_tpu_serve_shed_total with its
    deployment + reason tags (ISSUE 8: every shed stage is observable)."""
    import threading

    from ray_tpu import serve
    from ray_tpu.exceptions import BackPressureError

    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=0,
                      graceful_shutdown_timeout_s=3.0)
    class Busy:
        def __call__(self, request):
            time.sleep(1.5)
            return "ok"

    try:
        handle = serve.run(Busy.bind())
        occ = []
        t = threading.Thread(
            target=lambda: occ.append(
                handle.remote({}).result(timeout=60)))
        t.start()
        time.sleep(0.4)
        shed = 0
        for _ in range(3):
            try:
                handle.remote({}).result(timeout=10)
            except BackPressureError:
                shed += 1
        assert shed, "replica never shed while saturated"
        t.join(timeout=60)
        assert occ == ["ok"]
        # The replica flushes its registry to the GCS KV every ~2s; the
        # merged view must converge on the shed count.
        deadline = time.time() + 30
        counted = 0.0
        while time.time() < deadline:
            m = um.query_metrics().get("ray_tpu_serve_shed_total")
            if m:
                counted = sum(
                    v for tags, v in m["values"].items()
                    if dict(tags).get("deployment") == "Busy"
                    and dict(tags).get("reason") == "replica_capacity")
                if counted >= shed:
                    break
            time.sleep(1.0)
        assert counted >= shed, (counted, shed)
    finally:
        serve.shutdown()


# Runs LAST in this module: it clears the driver process's live metric
# values (the earlier live-contract test needs them intact).
def test_fork_reset_rekeys_and_clears_values():
    c = um.get_counter("test_fork_reset_counter")
    c.inc(5)
    old_key = um._process_key
    um._reset_after_fork()
    try:
        assert um._process_key != old_key  # never overwrite the parent's KV
        assert c._values == {}  # no double counting under the new key
        assert um._flusher_started is False
        # the next metric creation re-arms the flusher
        um.get_counter("test_fork_reset_counter2")
        assert um._flusher_started is True
    finally:
        um.flush()  # repopulate the driver's snapshot under the new key
