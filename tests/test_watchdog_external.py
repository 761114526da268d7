"""The out-of-process watchdog must kill a wedged pytest run in EVERY
phase — including ones the in-process SIGALRM watchdog cannot escape
(blocked signals, import-time hangs, non-daemon threads at interpreter
exit). Each case spawns a real pytest subprocess with tiny budgets and
asserts the killer SIGKILLs it (VERDICT r4 weak #1: two wedged suite runs
survived the in-process watchdog for 3.5h)."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFTEST = textwrap.dedent("""
    pytest_plugins = ["ray_tpu._private.pytest_watchdog"]
    import pytest

    @pytest.fixture
    def hang_setup():
        import tests_hang_helper as h
        h.hang_forever()
        yield

    @pytest.fixture
    def hang_teardown():
        yield
        import tests_hang_helper as h
        h.hang_forever()
""")

HELPER = textwrap.dedent("""
    import signal
    import time

    def hang_forever():
        # Defeat the in-process watchdog the way real wedges do: SIGALRM
        # blocked in this thread and ignored in any other that would take
        # it, so the per-test alarm can never fire.
        signal.pthread_sigmask(signal.SIG_BLOCK, [signal.SIGALRM])
        signal.signal(signal.SIGALRM, signal.SIG_IGN)
        while True:
            time.sleep(3600)
""")

CASES = {
    "collection": """
        import tests_hang_helper as h
        h.hang_forever()

        def test_never_reached():
            pass
    """,
    "setup": """
        def test_hang_in_setup(hang_setup):
            pass
    """,
    "call": """
        def test_hang_in_call():
            import tests_hang_helper as h
            h.hang_forever()
    """,
    "teardown": """
        def test_hang_in_teardown(hang_teardown):
            pass
    """,
    "exit": """
        def test_leak_nondaemon_thread():
            import threading, time
            t = threading.Thread(target=lambda: time.sleep(3600),
                                 daemon=False)
            t.start()
    """,
}


PLUGIN_ONLY = 'pytest_plugins = ["ray_tpu._private.pytest_watchdog"]\n'


def _run_with_limits(tmp_path, args, *, test_timeout, margin, exit_grace,
                     **more_env):
    """A pytest run in `tmp_path` with the plugin's limits from the
    environment; returns (exit code, output)."""
    env = dict(os.environ, **more_env)
    env.update({
        "RAY_TPU_TEST_TIMEOUT_S": str(test_timeout),
        "RAY_TPU_WATCHDOG_MARGIN_S": str(margin),
        "RAY_TPU_WATCHDOG_EXIT_GRACE_S": str(exit_grace),
        "RAY_TPU_WATCHDOG_DUMP_GRACE_S": "1",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
    })
    env.pop("RAY_TPU_NO_EXTERNAL_WATCHDOG", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *args],
        cwd=tmp_path, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, timeout=120)
    return proc.returncode, proc.stdout.decode(errors="replace")


@pytest.mark.parametrize("phase", sorted(CASES))
def test_killer_reaps_each_phase(tmp_path, phase):
    (tmp_path / "conftest.py").write_text(CONFTEST)
    (tmp_path / "tests_hang_helper.py").write_text(HELPER)
    (tmp_path / f"test_{phase}_case.py").write_text(
        textwrap.dedent(CASES[phase]))
    t0 = time.monotonic()
    try:
        code, out = _run_with_limits(
            tmp_path, [f"test_{phase}_case.py"], test_timeout=2, margin=2,
            exit_grace=3)
    except subprocess.TimeoutExpired:
        pytest.fail(f"watchdog never killed the {phase}-phase hang")
    took = time.monotonic() - t0
    # In the "exit" case pytest itself finished (its test passed); the KILL
    # lands on the wedged interpreter exit.
    assert code == -signal.SIGKILL, (code, out)
    assert took < 60, f"killer too slow: {took:.0f}s"


def test_killer_exits_when_target_finishes(tmp_path):
    """Clean runs must not leak killer processes or heartbeat files."""
    (tmp_path / "test_ok.py").write_text(
        "def test_ok():\n    assert 1 + 1 == 2\n")
    hb_dir = tmp_path / "hb"    # this run's heartbeat, apart from those
    hb_dir.mkdir()              # of the suites that run beside it
    code, out = _run_with_limits(
        tmp_path, ["test_ok.py", "-p", "ray_tpu._private.pytest_watchdog"],
        test_timeout=30, margin=120, exit_grace=60, TMPDIR=str(hb_dir))
    assert code == 0, out[-2000:]
    # the killer notices the dead pid and removes its heartbeat file
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and os.listdir(hb_dir):
        time.sleep(0.5)
    assert os.listdir(hb_dir) == []


def test_xdist_controller_outlives_the_stale_limit(tmp_path):
    """Under xdist the controller runs no test itself; its heartbeat is
    the workers' reports. A run of short tests that lasts several stale
    limits in all must end of itself with its tests passed (the tier-1
    run was killed at the limit, 720 s, whatever its tests did).

    The stale limit, 8 s, also has to cover the one stretch with no
    report in it: a worker's start and its collection, which took over
    3 s beside five other workers on a loaded machine (PR 38's run)."""
    (tmp_path / "conftest.py").write_text(PLUGIN_ONLY)
    (tmp_path / "test_many_short.py").write_text(textwrap.dedent("""
        import time
        import pytest

        @pytest.mark.parametrize("i", range(8))
        def test_short(i):
            time.sleep(2.5)
    """))
    code, out = _run_with_limits(
        tmp_path, ["-p", "xdist", "-n", "1", "test_many_short.py"],
        test_timeout=6, margin=2, exit_grace=20)
    assert code == 0, (code, out[-2000:])
    assert "8 passed" in out


def test_finished_xdist_worker_waits_for_its_controller_unharmed(tmp_path):
    """An xdist worker that is through with its files waits for the
    controller to end the session, for as long as the slowest worker
    needs. That is not a wedged interpreter: its killer stands down at
    sessionfinish (the tier-1 run had five of six workers stack-dumped
    and SIGKILLed a minute after they finished, PR 40's log)."""
    (tmp_path / "conftest.py").write_text(PLUGIN_ONLY)
    (tmp_path / "test_quick.py").write_text("def test_quick():\n    pass\n")
    (tmp_path / "test_long.py").write_text(textwrap.dedent("""
        import time
        import pytest

        @pytest.mark.parametrize("i", range(4))
        def test_two_seconds(i):
            time.sleep(2.0)
    """))
    # 8 s of test_long.py after test_quick.py's worker is done: four exit
    # graces, and the dump grace after them.
    code, out = _run_with_limits(
        tmp_path, ["-p", "xdist", "-n", "2", "--dist", "loadfile",
                   "test_quick.py", "test_long.py"],
        test_timeout=10, margin=5, exit_grace=2)
    assert code == 0, (code, out[-3000:])
    assert "5 passed" in out, out[-3000:]
    assert "[watchdog_killer]" not in out, out[-3000:]
    assert "most recent call first" not in out, out[-3000:]


def test_a_waiting_test_fails_alone_and_its_file_goes_on(tmp_path):
    """A test that sleeps past the phase limit is one failed test with the
    per-test watchdog's TimeoutError, after the limit and not after the
    killer's; the next test of the file runs and passes."""
    (tmp_path / "conftest.py").write_text(PLUGIN_ONLY)
    (tmp_path / "test_waits.py").write_text(textwrap.dedent("""
        import time

        def test_waits_for_what_never_comes():
            time.sleep(60)

        def test_after_it():
            assert True
    """))
    t0 = time.monotonic()
    code, out = _run_with_limits(
        tmp_path, ["test_waits.py"], test_timeout=2, margin=30,
        exit_grace=20)
    took = time.monotonic() - t0
    assert code == 1, (code, out[-3000:])
    assert "1 failed, 1 passed" in out, out[-3000:]
    assert "TimeoutError: test call exceeded 2s (per-test watchdog)" in out
    assert "[watchdog_killer]" not in out, out[-3000:]
    assert took < 30, f"the limit fired late: {took:.0f}s"
