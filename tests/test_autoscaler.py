"""Autoscaler tests (own module: builds a private cluster; must not share
the module-scoped cluster fixture)."""

import ray_tpu


def test_autoscaler_scales_up_for_pending_pg():
    """A pending placement group drives node launches until it schedules
    (reference: StandardAutoscaler reconcile + fake_multi_node provider)."""
    import threading

    from ray_tpu.autoscaler import Autoscaler, LocalNodeProvider
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util import placement_group, remove_placement_group

    c = Cluster(head_node_args={"num_cpus": 1, "node_name": "head",
                                "object_store_memory": 128 * 1024 * 1024})
    try:
        c.connect()
        provider = LocalNodeProvider(c.head_node,
                                     default_resources={"CPU": 2.0})
        scaler = Autoscaler(provider, min_workers=0, max_workers=3,
                            idle_timeout_s=300.0, interval_s=1.0)
        scaler.start()
        try:
            # 4 CPUs of bundles cannot fit the 1-CPU head: must scale up.
            pg = placement_group([{"CPU": 2.0}, {"CPU": 2.0}],
                                 strategy="SPREAD")
            assert pg.ready(timeout=120), "autoscaler never satisfied the PG"
            assert len(provider.nodes()) >= 2
            remove_placement_group(pg)
        finally:
            scaler.stop()
            # the nodes it launched are the provider's, not the cluster's:
            # left up, their nodelets outlive the GCS until this process
            # exits (and for good when it is killed)
            for node in provider.nodes():
                provider.terminate_node(node)
    finally:
        ray_tpu.shutdown()
        c.shutdown()
