"""Out-of-process test watchdog: SIGKILLs a wedged pytest process.

The in-process SIGALRM watchdog (pytest_watchdog.py) covers armed test
phases, but cannot save a process that hangs during collection, inside a
session fixture, or at interpreter exit (leaked non-daemon threads keep
the interpreter alive after pytest_sessionfinish) — and a main thread
stuck in uninterruptible C code never runs the alarm handler at all. This
killer runs as a SEPARATE process, so no in-process state can mask it.

Protocol: the monitored process touches ``heartbeat_path`` (mtime) at
every test-phase boundary and writes ``done`` into it at sessionfinish.
If the heartbeat goes stale for longer than ``stale_limit`` seconds
(or ``exit_grace`` seconds after ``done``), the killer sends SIGUSR1
(faulthandler stack dump for forensics), waits ``dump_grace``, then
SIGKILLs the pid. It exits on its own when the target dies, or when the
heartbeat file is removed: that is how an xdist worker, which waits for
its controller after its own session, stands its killer down.

Usage: ``python -m ray_tpu._private.watchdog_killer <pid> <heartbeat>
<stale_limit_s> <exit_grace_s> [dump_grace_s]``

Reference: pytest-timeout's thread/signal methods share the monitored
process and have the same blind spots; ray's CI uses external bazel test
timeouts for the same reason.
"""

import os
import signal
import sys
import time


def _alive(pid: int) -> bool:
    """False once the process has exited, reaped or not. A parent that
    reads its child's output to the end before it reaps the child waits
    for this killer too, which holds the same pipe: the zombie must count
    as dead, or the two wait for each other until the exit grace. Read
    from /proc, so Linux only, as the cluster's own process handling is:
    without /proc every pid reads as gone and the killer exits at once."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rpartition(")")[2].split()[0] != "Z"
    except (FileNotFoundError, ProcessLookupError):
        return False  # gone, before the open or between it and the read


def main() -> None:
    pid = int(sys.argv[1])
    hb = sys.argv[2]
    stale_limit = float(sys.argv[3])
    exit_grace = float(sys.argv[4])
    dump_grace = float(sys.argv[5]) if len(sys.argv) > 5 else 10.0

    while True:
        time.sleep(min(2.0, stale_limit / 4))
        if not _alive(pid):
            break
        try:
            st = os.stat(hb)
            with open(hb) as f:
                done = f.read().strip() == "done"
        except OSError:
            break  # heartbeat file removed: the monitored run stood us down
        age = time.time() - st.st_mtime
        if age <= (exit_grace if done else stale_limit):
            continue
        # Wedged. Stack-dump, grace, kill.
        try:
            os.kill(pid, signal.SIGUSR1)
        except OSError:
            break
        time.sleep(dump_grace)
        if _alive(pid):
            sys.stderr.write(
                f"[watchdog_killer] pid {pid} heartbeat stale "
                f"{age:.0f}s (limit {stale_limit:.0f}s"
                f"{', session done' if done else ''}); SIGKILL\n")
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        break
    try:
        os.unlink(hb)
    except OSError:
        pass


if __name__ == "__main__":
    main()
