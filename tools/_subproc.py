"""Shared fresh-subprocess runner for the measurement tools.

feasibility_1p3b.py and feasibility_xl.py isolate each measurement in
a fresh interpreter (device-buffer hygiene / per-process device
counts). One copy of the harness: run the tool script with a flag +
JSON spec, parse the last stdout line as the result, degrade failures
(including hangs) to an {"error": ...} record instead of killing the
whole sweep.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Callable, Optional


def run_spec(tool_path: str, flag: str, spec: dict, timeout: int,
             retries: int = 1,
             retry_if: Optional[Callable[[str], bool]] = None) -> dict:
    """Run ``python tool_path <flag> <json-spec>`` in a fresh process.

    Returns the last stdout line parsed as JSON on success, else an
    ``{"error": ...}`` record (spec included). ``retry_if(err)`` gates
    re-running on transient failures; the final attempt never sleeps.
    """
    import time
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(tool_path)))
    last = None
    for attempt in range(retries + 1):
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(tool_path), flag,
                 json.dumps(spec)],
                capture_output=True, text=True, timeout=timeout,
                cwd=repo_root)
        except subprocess.TimeoutExpired:
            last = {"spec": spec, "error": f"timeout {timeout}s"}
            break  # a hang is not transient; don't re-hang
        if proc.returncode == 0:
            try:
                return json.loads(proc.stdout.strip().splitlines()[-1])
            except (ValueError, IndexError):
                last = {"spec": spec,
                        "error": "no JSON on child stdout: "
                                 + proc.stdout.strip()[-300:]}
                break
        err = (proc.stderr.strip() or "nonzero exit")[-800:]
        last = {"spec": spec, "error": err}
        if retry_if is None or not retry_if(err) or attempt == retries:
            break
        time.sleep(10)
    return last
