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

# Out-of-process killer: SIGKILLs this pytest process if a phase wedges
# past the per-test budget + margin, or if the interpreter fails to exit
# after the session (leaked non-daemon threads) — states the in-process
# SIGALRM watchdog below cannot escape.
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


# ---------------------------------------------------------------------------
# Per-test watchdog (reference: pytest.ini's 180s default per-test timeout).
# No pytest-timeout in this image, so a SIGALRM in the main thread turns a
# hung test into a failure with a traceback instead of wedging the suite.
# ---------------------------------------------------------------------------
TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT_S", "600"))

# kill -USR1 <pytest pid> dumps every thread's stack (hang forensics).
import faulthandler as _faulthandler
import signal as _signal

_faulthandler.register(_signal.SIGUSR1, all_threads=True)


def _watchdog(phase):
    import contextlib
    import faulthandler
    import signal
    import sys

    @contextlib.contextmanager
    def guard():
        def _alarm(signum, frame):
            faulthandler.dump_traceback(file=sys.stderr)
            # Re-arm BEFORE raising: if a broad except inside the test
            # swallows this TimeoutError, the next alarm still fires —
            # one-shot alarms leave the rest of the phase unguarded.
            signal.alarm(TEST_TIMEOUT_S)
            raise TimeoutError(
                f"test {phase} exceeded {TEST_TIMEOUT_S}s "
                f"(per-test watchdog)")

        old = signal.signal(signal.SIGALRM, _alarm)
        signal.alarm(TEST_TIMEOUT_S)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)

    return guard()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _watchdog("call"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    # Fixture setup (cluster boot) hangs must surface too.
    with _watchdog("setup"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    # Fixture/module teardown (ray_tpu.shutdown) hangs must surface too.
    with _watchdog("teardown"):
        yield
