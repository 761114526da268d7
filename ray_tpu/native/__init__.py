"""Native (C++) components, built on demand with g++.

The compiled artifacts are cached next to the sources; a content hash of the
source file invalidates the cache on change.
"""

from __future__ import annotations

import fcntl
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_build_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    pass


def build_library(source_name: str, extra_flags: tuple = ()) -> str:
    """Compile ``<source_name>.cc`` into ``lib<source_name>.so`` and return
    its path. No-op if the cached build is current."""
    src = os.path.join(_NATIVE_DIR, f"{source_name}.cc")
    lib = os.path.join(_NATIVE_DIR, f"lib{source_name}.so")
    stamp = os.path.join(_NATIVE_DIR, f".{source_name}.hash")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + repr(extra_flags).encode()).hexdigest()

    def current() -> bool:
        if os.path.exists(lib) and os.path.exists(stamp):
            with open(stamp) as f:
                return f.read().strip() == digest
        return False

    if current():
        return lib
    # On a fresh checkout the GCS, the nodelet and every worker reach this
    # at once: the thread lock orders this process, the file lock orders
    # the processes, and whoever waited finds the build done.
    with _build_lock, open(os.path.join(
            _NATIVE_DIR, f".{source_name}.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if current():
            return lib
        tmp = f"{lib}.{os.getpid()}.tmp"
        cmd = [
            "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
            "-o", tmp, src, "-lpthread", *extra_flags,
        ]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise NativeBuildError(
                f"g++ failed for {source_name}:\n{proc.stderr}"
            )
        os.replace(tmp, lib)
        with open(stamp, "w") as f:
            f.write(digest)
    return lib
