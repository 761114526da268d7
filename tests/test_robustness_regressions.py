"""Regressions for the ISSUE 5 robustness satellites (ADVICE round 5):
anonymous-actor registration race, PlacementGroup handle pickling,
bounded kill-actor tombstones."""

import asyncio
import os
import pickle
import subprocess
import sys

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# worker.py _ensure_client: get_actor -> None while our register_actor is
# still in flight means PENDING, not "was never created".
# ---------------------------------------------------------------------------
REGISTRATION_RACE_SCRIPT = """
import os
# Delay ONLY the registration RPC's send path: the first actor task's
# get_actor then always wins the race to the GCS.
os.environ["RAY_TPU_CHAOS_SEED"] = "3"
os.environ["RAY_TPU_CHAOS_DELAY_MS"] = "register_actor=400:700"
import ray_tpu

ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)

@ray_tpu.remote
class A:
    def ping(self):
        return "pong"

a = A.remote()  # anonymous: fire-and-forget registration
# Immediately calling must NOT raise ActorDiedError("was never created")
assert ray_tpu.get(a.ping.remote(), timeout=120) == "pong"
print("RACE_OK", flush=True)
ray_tpu.shutdown()
"""


def test_anonymous_actor_survives_delayed_registration():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", REGISTRATION_RACE_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert "RACE_OK" in out.stdout, out.stdout[-800:] + out.stderr[-2000:]


# ---------------------------------------------------------------------------
# util/placement_group.py: handles must pickle while the async create RPC
# future is still attached (futures hold thread locks).
# ---------------------------------------------------------------------------
def test_placement_group_handle_picklable_with_inflight_create(
        ray_start_regular):
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)

    pg = placement_group([{"CPU": 1.0}], strategy="PACK")
    try:
        # Pickle BEFORE ready(): _create_fut is still attached here.
        blob = pickle.dumps(pg)
        assert pg.ready(timeout=60)

        clone = pickle.loads(blob)
        assert clone.id == pg.id
        assert clone.bundle_specs == pg.bundle_specs
        assert clone._create_fut is None

        @ray_tpu.remote
        def describe(g):
            return (g.id.hex(), g.bundle_count)

        # The reference-supported pattern: hand the PG handle to a task.
        assert ray_tpu.get(describe.remote(pg), timeout=60) == \
            (pg.id.hex(), 1)
    finally:
        remove_placement_group(pg)


# ---------------------------------------------------------------------------
# core/gcs.py: repeated kills of bogus ids must not grow _prekilled forever.
# ---------------------------------------------------------------------------
def test_prekilled_tombstones_bounded(tmp_path):
    from ray_tpu._private.ids import ActorID, JobID
    from ray_tpu.core.gcs import GcsServer

    gcs = GcsServer(persist_path=None)

    async def flood():
        for _ in range(gcs.PREKILL_MAX + 500):
            aid = ActorID.of(JobID.from_int(1))
            await gcs.rpc_kill_actor(actor_id=aid.binary())
        return len(gcs._prekilled)

    size = asyncio.run(flood())
    assert size <= gcs.PREKILL_MAX, size

    # a tombstoned registration still lands dead (the tombstone works)
    async def tombstone_then_register():
        aid = ActorID.of(JobID.from_int(2))
        await gcs.rpc_kill_actor(actor_id=aid.binary())
        spec = pickle.dumps(None)  # never scheduled: dead on arrival
        reply = await gcs.rpc_register_actor(
            actor_id=aid.binary(), creation_spec=spec)
        return reply, gcs.actors[aid].state

    reply, state = asyncio.run(tombstone_then_register())
    assert reply["ok"] and state == "DEAD"
