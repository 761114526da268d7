"""Pytest plugin: arms the out-of-process watchdog_killer for the run.

Load with ``pytest_plugins = ["ray_tpu._private.pytest_watchdog"]`` (the
repo's tests/conftest.py does). The plugin heartbeats at every test-phase
boundary (and, in an xdist controller, at every report a worker sends);
the external killer SIGKILLs the whole pytest process if a
phase wedges past the stale limit, or if the interpreter fails to exit
within the exit grace after the session finished (leaked non-daemon
threads). See watchdog_killer.py for why this must live out-of-process.

Env knobs:
- RAY_TPU_TEST_TIMEOUT_S       per-test budget (default 600)
- RAY_TPU_WATCHDOG_MARGIN_S    killer fires this much past the budget
                               (default 120 — lets the in-process
                               watchdog try first)
- RAY_TPU_WATCHDOG_EXIT_GRACE_S  post-sessionfinish exit budget (60)
- RAY_TPU_NO_EXTERNAL_WATCHDOG=1 disable (nested pytest-in-test runs)
"""

import os
import subprocess
import sys
import tempfile

import pytest

_hb_path = None


def _touch() -> None:
    if _hb_path is not None:
        try:
            os.utime(_hb_path)
        except OSError:
            pass


def pytest_configure(config):
    global _hb_path
    if os.environ.get("RAY_TPU_NO_EXTERNAL_WATCHDOG") == "1":
        return
    # The killer's pre-kill SIGUSR1 must dump stacks, not terminate us
    # (SIGUSR1's default action) — forensics live here so every consumer
    # of the plugin gets them.
    import faulthandler
    import signal

    faulthandler.register(signal.SIGUSR1, all_threads=True)
    timeout = float(os.environ.get("RAY_TPU_TEST_TIMEOUT_S", "600"))
    margin = float(os.environ.get("RAY_TPU_WATCHDOG_MARGIN_S", "120"))
    exit_grace = float(
        os.environ.get("RAY_TPU_WATCHDOG_EXIT_GRACE_S", "60"))
    dump_grace = float(
        os.environ.get("RAY_TPU_WATCHDOG_DUMP_GRACE_S", "10"))
    fd, _hb_path = tempfile.mkstemp(prefix="ray_tpu_test_hb_")
    os.close(fd)
    env = dict(os.environ)
    # The killer must never inherit a JAX/TPU reservation.
    env["JAX_PLATFORMS"] = "cpu"
    config._ray_tpu_killer = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.watchdog_killer",
         str(os.getpid()), _hb_path, str(timeout + margin),
         str(exit_grace), str(dump_grace)],
        start_new_session=True, env=env,
        stdout=subprocess.DEVNULL, stderr=None)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    _touch()
    yield
    _touch()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    _touch()
    yield
    _touch()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    _touch()
    yield
    _touch()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    _touch()
    yield
    _touch()


def pytest_runtest_logreport(report):
    # Under xdist the controller runs no test phase of its own: a worker's
    # report is its sign of life. Without it the controller's heartbeat
    # was never touched, and a whole run longer than the stale limit was
    # killed however well its tests went.
    _touch()


def pytest_sessionfinish(session, exitstatus):
    # Flip the killer to exit-grace mode: from here the process must
    # actually terminate, or leaked non-daemon threads get it killed.
    if _hb_path is not None:
        try:
            with open(_hb_path, "w") as f:
                f.write("done")
        except OSError:
            pass
