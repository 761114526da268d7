"""Test fixtures (reference: python/ray/tests/conftest.py — ray_start_regular
etc. built on cluster_utils starting real processes per simulated node).

JAX tests run on a virtual 8-device CPU mesh: env must be set before jax is
first imported anywhere in the test process.
"""

import os

# Virtual 8-device CPU mesh; JAX reads both settings when it is imported.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest

# One time limit a test phase (a SIGALRM that fails the test that waits),
# and behind it an out-of-process killer for what no alarm can escape.
pytest_plugins = ["ray_tpu._private.pytest_watchdog"]


@pytest.fixture(autouse=True)
def _reap_leaked_channel_dags():
    """A test that leaks a channel-mode compiled DAG leaves pinned actor
    loops blocked on rings that can wedge every later test; contain the
    blast radius to the leaking test."""
    yield
    from ray_tpu.dag import teardown_all_channel_dags

    leaked = teardown_all_channel_dags()
    if leaked:
        import warnings

        warnings.warn(f"test leaked {leaked} channel-mode DAG(s); "
                      "torn down by conftest")


@pytest.fixture(scope="module")
def ray_cluster():
    """A live single-node cluster (GCS + nodelet subprocesses), shared per
    test module for speed; small object store to keep startup fast."""
    import ray_tpu

    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular(ray_cluster):
    return ray_cluster
