"""Export-event framework (reference: src/ray/util/event.h RayExportEvent
+ export_*.proto): components write durable JSONL event files under the
session's export_events/ dir for external ingestion."""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu._private.export_events import ExportEventLogger


def test_logger_writes_and_rotates(tmp_path):
    log = ExportEventLogger(str(tmp_path), max_bytes=600)
    for i in range(12):
        log.emit("EXPORT_ACTOR", {"i": i, "pad": "x" * 40})
    log.close()
    main = tmp_path / "event_EXPORT_ACTOR.log"
    backup = tmp_path / "event_EXPORT_ACTOR.log.1"
    assert main.exists() and backup.exists(), "rotation never happened"
    rows = [json.loads(l) for p in (backup, main)
            for l in p.read_text().splitlines()]
    got = [r["event_data"]["i"] for r in rows]
    # one-backup rotation: the TAIL of the stream survives, in order
    assert got == list(range(12))[-len(got):] and len(got) >= 4, got
    assert all(r["source_type"] == "EXPORT_ACTOR" for r in rows)
    assert all("event_id" in r and "timestamp" in r for r in rows)
    with pytest.raises(ValueError):
        log.emit("EXPORT_BOGUS", {})


def test_cluster_writes_export_events(ray_start_regular):
    """A live cluster's GCS exports node/actor/task transitions that an
    external consumer can tail from disk."""

    @ray_tpu.remote
    class Probe:
        def ping(self):
            return 1

    @ray_tpu.remote
    def task():
        return 1

    a = Probe.remote()
    assert ray_tpu.get(a.ping.remote()) == 1
    assert ray_tpu.get(task.remote()) == 1
    ray_tpu.kill(a)

    from ray_tpu._private import worker as wm

    # The GCS writes next to its persist path inside the session dir. Read
    # this cluster's own files: sessions that earlier runs left under /tmp
    # hold events of other tasks.
    session_dir = wm.global_worker().session_dir
    import glob

    deadline = time.monotonic() + 30
    actor_rows = node_rows = task_rows = []
    while time.monotonic() < deadline:
        files = glob.glob(os.path.join(session_dir, "export_events",
                                       "event_EXPORT_*.log"))
        by_type = {}
        for f in files:
            kind = os.path.basename(f)[len("event_"):-len(".log")]
            by_type.setdefault(kind, []).extend(
                json.loads(l) for l in open(f).read().splitlines())
        actor_rows = by_type.get("EXPORT_ACTOR", [])
        node_rows = by_type.get("EXPORT_NODE", [])
        task_rows = by_type.get("EXPORT_TASK", [])
        if (any(r["event_data"].get("state") == "DEAD"
                for r in actor_rows) and node_rows and task_rows):
            break
        time.sleep(0.5)
    assert node_rows, "no node export events"
    states = {r["event_data"].get("state") for r in actor_rows}
    assert {"ALIVE", "DEAD"} <= states, states
    assert any(r["event_data"].get("name") == "task"
               for r in task_rows), "task event not exported"
