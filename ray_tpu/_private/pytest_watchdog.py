"""Pytest plugin: one time limit a test phase, and a killer behind it.

Load with ``pytest_plugins = ["ray_tpu._private.pytest_watchdog"]`` (the
repo's tests/conftest.py does). The rule, whole:

1. Every phase of every test (setup, call, teardown) has one limit,
   ``TEST_TIMEOUT_S``, far under any run's. A SIGALRM in the main thread
   turns a phase that waits into one failed test with a traceback; the
   rest of its file still runs. (No pytest-timeout in this image.)
2. Only where that alarm cannot fire (blocked signals, a hang at import
   or in uninterruptible C code, non-daemon threads at interpreter exit)
   is the process killed from outside: the plugin heartbeats at every
   phase boundary (in an xdist controller, at every report a worker
   sends), and watchdog_killer.py SIGKILLs the process once the heartbeat
   is ``TEST_TIMEOUT_S`` + margin old, or when the interpreter is still
   there an exit grace after the session finished.
3. A process that has finished its work is never killed for waiting on
   its controller: an xdist worker whose session is over stands its
   killer down. Its exit is the controller's to order (xdist terminates
   its workers, and kills the ones that do not go), and the controller
   has a killer of its own.

Env knobs:
- RAY_TPU_TEST_TIMEOUT_S       limit of one test phase (default 240)
- RAY_TPU_WATCHDOG_MARGIN_S    killer fires this much past the limit
                               (default 120 — lets the in-process
                               alarm try first)
- RAY_TPU_WATCHDOG_EXIT_GRACE_S  post-sessionfinish exit budget (60)
- RAY_TPU_NO_EXTERNAL_WATCHDOG=1 no killer (nested pytest-in-test runs)
"""

import contextlib
import faulthandler
import os
import signal
import subprocess
import sys
import tempfile

import pytest

# Four to six times the slowest test of the tier-1 run under -n 6 (49 s
# on the builders' machine, some 80 s on the driver's; CHANGES.md, PR 40).
TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT_S", "240"))

# kill -USR1 <pytest pid> dumps every thread's stack (hang forensics, and
# the killer's last word before SIGKILL): SIGUSR1's default action would
# terminate us instead.
faulthandler.register(signal.SIGUSR1, all_threads=True)

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
         str(os.getpid()), _hb_path, str(TEST_TIMEOUT_S + margin),
         str(exit_grace), str(dump_grace)],
        start_new_session=True, env=env,
        stdout=subprocess.DEVNULL, stderr=None)


@contextlib.contextmanager
def _phase(name):
    """One test phase: heartbeat at both ends, the alarm in between."""

    def _alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr)
        # Re-arm BEFORE raising: if a broad except inside the test
        # swallows this TimeoutError, the next alarm still fires —
        # one-shot alarms leave the rest of the phase unguarded.
        signal.alarm(TEST_TIMEOUT_S)
        raise TimeoutError(
            f"test {name} exceeded {TEST_TIMEOUT_S}s (per-test watchdog)")

    _touch()
    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        _touch()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    # Fixture setup (cluster boot) hangs must surface too.
    with _phase("setup"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _phase("call"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    # Fixture/module teardown (ray_tpu.shutdown) hangs must surface too.
    with _phase("teardown"):
        yield


def pytest_runtest_logreport(report):
    # Under xdist the controller runs no test phase of its own: a worker's
    # report is its sign of life. Without it the controller's heartbeat
    # was never touched, and a whole run longer than the stale limit was
    # killed however well its tests went.
    _touch()


def pytest_sessionfinish(session, exitstatus):
    if _hb_path is None:
        return
    try:
        if hasattr(session.config, "workerinput"):
            # An xdist worker: done with its files, it waits for the
            # controller to end the session, however long the other
            # workers take. No heartbeat file tells the killer to go.
            os.unlink(_hb_path)
        else:
            # Flip the killer to exit-grace mode: from here the process
            # must actually terminate, or leaked non-daemon threads get
            # it killed.
            with open(_hb_path, "w") as f:
                f.write("done")
    except OSError:
        pass
