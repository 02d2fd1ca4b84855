"""From a profiler trace (``.xplane.pb``) to device busy and idle time, time
per program, the operations that took most time and the longest idle gaps by
what the host was doing. Uses ``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 23; ``tests/benchmark/data``):
one plane a chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one
event an execution of a compiled program, named ``jit_<function>(<hash>)``)
and ``XLA Ops`` (its operations; a ``while`` holds its body's events nested on
the same line), and one plane ``/host:CPU`` with a line a thread. Device and
host lines run on clocks that differ by some milliseconds: they are aligned by
the executions' ``run_id``, which the host's ``CompleteCallbacks`` events
repeat.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

MIN_GAP_S = 50e-6
_WAITS = ("ReadSyncFlag", "futex", "Acquire semaphore", "Release semaphore")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program_of(module_event_name: str) -> str:
    """``jit_mixed_fn(123)`` -> ``mixed_fn``."""
    m = re.match(r"(?:jit_|pmap_)?(.*?)(?:\(\d+\))?$", module_event_name)
    return m.group(1)


def op_of(op_event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return op_event_name.split(" = ")[0].lstrip("%").strip()


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def self_times(events):
    """``events``: ``(start, end, name)`` of one line, where a container
    (``while``, ``conditional``) holds its children. Yields ``(name, seconds
    not covered by the events nested directly inside)``."""
    stack = []      # [start, end, name, covered]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and a >= stack[-1][1]:
            s = stack.pop()
            yield s[2], (s[1] - s[0]) - s[3]
        if stack:
            stack[-1][3] += min(b, stack[-1][1]) - a
        stack.append([a, b, name, 0.0])
    while stack:
        s = stack.pop()
        yield s[2], (s[1] - s[0]) - s[3]


def _events(line):
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name,
             e) for e in line.events]


def reduce_file(path: str) -> dict:
    import jax
    return reduce(jax.profiler.ProfileData.from_file(path))


def reduce(pd) -> dict:
    """``{"devices": [...], "busy_s", "window_s", "programs": {name:
    [seconds, ...]}, "top_ops": [[name, s]], "idle_gaps": [[what, s]]}``;
    busy and the window are averaged over the device planes that ran
    something."""
    devices, host = [], []
    run_end = {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            mods = _events(lines["XLA Modules"]) if "XLA Modules" in lines \
                else []
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            if not mods and not ops:
                continue
            for a, b, _, e in mods:
                rid = dict(e.stats).get("run_id")
                if rid is not None:
                    run_end[(plane.name, rid)] = b
            devices.append({"name": plane.name, "modules": mods, "ops": ops})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((a, b, name, e) for a, b, name, e in _events(ln))
    # clock offset: host time = device time + offset
    offs = []
    for a, b, name, e in host:
        if name == "CompleteCallbacks":
            st = dict(e.stats)
            for dev in devices:
                end = run_end.get((dev["name"], st.get("run_id")))
                if end is not None:
                    offs.append(a - end)
    offset = min(offs) if offs else 0.0
    spans = sorted((a - offset, b - offset, name) for a, b, name, _ in host
                   if b > a and not name.startswith(_WAITS)
                   and not name.startswith("$"))
    starts = [s[0] for s in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)

    def blame(a, b):
        """The host event that covers most of an idle gap; an enclosing
        annotation of the benchmark's wins over what it encloses."""
        best, best_ov, note = None, 0.0, None
        i = bisect.bisect_left(starts, a - longest)
        while i < len(spans) and spans[i][0] < b:
            sa, sb, name = spans[i]
            ov = min(b, sb) - max(a, sa)
            if ov > 0:
                if name.startswith("bench.") and (note is None
                                                  or ov > note[1]):
                    note = (name, ov)
                if ov > best_ov:
                    best, best_ov = name, ov
            i += 1
        if note is not None and note[1] >= 0.3 * (b - a):
            return note[0]
        if best is None or best_ov < 0.3 * (b - a):
            return "host code with no span"
        return best

    programs = defaultdict(list)
    op_s = defaultdict(float)
    gap_s = defaultdict(float)
    busy_sum = window_sum = 0.0
    for dev in devices:
        src = dev["ops"] or dev["modules"]
        busy = union((a, b) for a, b, _, _ in src)
        lo = min(a for a, _ in busy)
        hi = max(b for _, b in busy)
        dev["busy_s"] = sum(b - a for a, b in busy)
        dev["window_s"] = hi - lo
        busy_sum += dev["busy_s"]
        window_sum += dev["window_s"]
        for a, b, name, _ in dev["modules"]:
            programs[program_of(name)].append(b - a)
        mods = sorted((a, b, program_of(n)) for a, b, n, _ in dev["modules"])
        mstarts = [m[0] for m in mods]
        named = []
        for a, b, name, _ in dev["ops"]:
            i = bisect.bisect_right(mstarts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            named.append((a, b, f"{prog}/{op_of(name)}"))
        for name, s in self_times(named):
            op_s[name] += s
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            if s1 - e0 >= MIN_GAP_S:
                gap_s[blame(e0, s1)] += s1 - e0
            else:
                gap_s["between operations (under 50 us each)"] += s1 - e0
    n = max(len(devices), 1)
    top = lambda dct: [[k, v / n] for k, v in sorted(  # noqa: E731
        dct.items(), key=lambda kv: -kv[1])[:10]]
    return {"devices": [{"name": dv["name"], "busy_s": dv["busy_s"],
                         "window_s": dv["window_s"]} for dv in devices],
            "busy_s": busy_sum / n, "window_s": window_sum / n,
            "clock_offset_s": offset, "programs": dict(programs),
            "collective_exposed_s": collective_exposed_s(pd),
            "top_ops": top(op_s), "idle_gaps": top(gap_s)}


def collective_exposed_s(pd) -> float:
    """Seconds, averaged over device planes, in which a collective operation
    ran on a device and no other operation did."""
    total, n = 0.0, 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        coll, comp = [], []
        for a, b, name, _ in _events(lines["XLA Ops"]):
            op = op_of(name)
            if op.startswith(("while", "conditional")):
                continue
            (coll if op.startswith(COLLECTIVES) else comp).append((a, b))
        if not coll and not comp:
            continue
        n += 1
        comp = union(comp)
        cstarts = [c[0] for c in comp]
        for a, b in union(coll):
            covered = 0.0
            i = max(bisect.bisect_right(cstarts, a) - 1, 0)
            while i < len(comp) and comp[i][0] < b:
                covered += max(0.0, min(b, comp[i][1]) - max(a, comp[i][0]))
                i += 1
            total += (b - a) - covered
    return total / max(n, 1)


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
