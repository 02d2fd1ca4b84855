"""Shared build-on-first-use helper for the native (.cc → .so) pieces.

One place for the compile command, the rebuild check and the
``PTDF_CC`` compiler override used by the datafeed, the sparse
accessor, the PJRT predictor and any future native module.

The ``.so`` files are build products: git ignores them and a checkout
has none, so first use compiles them from the ``.cc`` beside them. A
library is rebuilt when its source is newer than the local build. The
compile writes a temporary file and renames it into place, so several
processes (test workers, a fleet's replicas) may race to the first
build.
"""

from __future__ import annotations

import os
import subprocess
import threading

_BUILD_LOCK = threading.Lock()


def build_native_lib(src: str, so: str, extra_flags=()) -> str:
    """Compile ``src`` to ``so`` if missing/stale; returns ``so``.
    Raises on compile failure — callers decide whether that is fatal
    (datafeed) or degrades to a Python path (accessor)."""
    with _BUILD_LOCK:
        if (not os.path.exists(so) or
                os.path.getmtime(so) < os.path.getmtime(src)):
            cc = os.environ.get("PTDF_CC", "g++")
            tmp = f"{so}.{os.getpid()}.tmp"
            cmd = [cc, "-O2", "-std=c++17", "-shared", "-fPIC",
                   "-pthread", *extra_flags, src, "-o", tmp]
            try:
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
    return so
