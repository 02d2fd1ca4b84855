"""Device self time per program per ``jax.named_scope``, from an
``.xplane.pb`` profiler trace.

The answer to "where inside the 390 ms tick": XLA names a fused operation
``fusion.123``, but every operation's metadata carries the scope path it was
traced under (``jit(mixed_fn)/while/body/kv_gather/gather``) as its ``tf_op``
stat, and a pallas kernel its ``name=``. ``jax.profiler.ProfileData`` yields
only an event's own stats, not its metadata's, and no compiled ``xplane_pb2``
ships with jax, so this module decodes the few fields it needs from the
protobuf wire format itself (``XSpace``/``XPlane``/``XLine``/``XEvent``/
``XEventMetadata``/``XStat``, tsl/profiler/protobuf/xplane.proto). Stdlib
only; reads any trace the JAX profiler wrote, on any machine.

    python tools/trace_scopes.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import struct
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

# scope-path components that say how an operation was reached, not which
# part of the model it belongs to: transforms keep what they wrap, the
# jitted helpers of jax.numpy (``jit(_take)``) and control flow are dropped
_TRANSFORM = re.compile(
    r"^(jvp|vmap|pmap|transpose|checkpoint|remat|rematted_computation"
    r"|custom_jvp|custom_vjp|custom_vjp_call|custom_vjp_call_jaxpr"
    r"|shard_map|named)\((.*)\)$")
_DROPPED = re.compile(
    r"^((jit|pjit|closed_call|core_call)\(.*\)|while|body|cond|scan"
    r"|closed_call|core_call|checkpoint|branch_\d+_fun)$")


# -- protobuf wire format ----------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for
    varint and fixed fields, a ``bytes`` slice for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane message")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes) -> Tuple[int, object]:
    """An ``XStat``: ``(metadata id, value)``; a ``ref_value`` comes back as
    ``("ref", id)`` for the plane's stat-metadata names to resolve."""
    mid, val = 0, None
    for num, wt, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            val = ("ref", v)
    return mid, val


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


class Plane:
    """One ``XPlane``: ``name``, ``lines`` (``{name: [(start_s, end_s,
    metadata id)]}``), ``event_names`` and ``event_stats`` by metadata id
    (stat names resolved, ``ref`` values too)."""

    def __init__(self, buf: bytes):
        self.name = ""
        stat_names: Dict[int, str] = {}
        raw_events: Dict[int, bytes] = {}
        raw_lines: List[bytes] = []
        for num, _, v in _fields(buf):
            if num == 2:
                self.name = bytes(v).decode()
            elif num == 3:
                raw_lines.append(v)
            elif num == 4:
                k, m = _map_entry(v)
                raw_events[k] = m
            elif num == 5:
                k, m = _map_entry(v)
                stat_names[k] = next(
                    (bytes(x).decode() for n, _, x in _fields(m) if n == 2),
                    "")
        self.event_names: Dict[int, str] = {}
        self.event_stats: Dict[int, Dict[str, object]] = {}
        for mid, m in raw_events.items():
            stats = {}
            for num, _, v in _fields(m):
                if num == 2:
                    self.event_names[mid] = bytes(v).decode("utf-8",
                                                            "replace")
                elif num == 5:
                    sid, val = _stat(v)
                    if isinstance(val, tuple):
                        val = stat_names.get(val[1], "")
                    stats[stat_names.get(sid, str(sid))] = val
            self.event_stats[mid] = stats
        self.lines: Dict[str, List[Tuple[float, float, int]]] = {}
        for ln in raw_lines:
            name, t0_ns, events = "", 0, []
            for num, _, v in _fields(ln):
                if num == 2:
                    name = bytes(v).decode()
                elif num == 3:
                    t0_ns = _signed(v)
                elif num == 4:
                    events.append(v)
            out = []
            for ev in events:
                mid = off_ps = dur_ps = 0
                for num, _, v in _fields(ev):
                    if num == 1:
                        mid = v
                    elif num == 2:
                        off_ps = _signed(v)
                    elif num == 3:
                        dur_ps = _signed(v)
                start = t0_ns * 1e-9 + off_ps * 1e-12
                out.append((start, start + dur_ps * 1e-12, mid))
            self.lines[name] = out


def read_planes(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [Plane(v) for num, _, v in _fields(buf) if num == 1]


def find_xplane(path: str) -> str:
    """``path`` itself, or the newest ``*.xplane.pb`` under a directory."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


# -- scopes ------------------------------------------------------------------

def op_path(tf_op: Optional[str]) -> str:
    """The ``tf_op`` stat without its trailing ``:<type>``:
    ``jit(probe_scan)/while/body/closed_call/dot_general``."""
    return (tf_op or "").rsplit(":", 1)[0]


def scope_of(tf_op: Optional[str]) -> str:
    """``jit(mixed_fn)/while/body/kv_gather/take:`` -> ``kv_gather``: the
    scope path without the program, the control-flow and transform wrappers
    and the trailing primitive; ``(no scope)`` when nothing is left. A
    transform keeps what it wraps (``transpose(jvp(attn))`` -> ``attn``),
    so forward and backward operations of one scope add up."""
    parts = op_path(tf_op).split("/")[1:-1]  # jit(<program>)/.../<primitive>
    keep = []
    for part in parts:
        while True:
            m = _TRANSFORM.match(part)
            if m is None:
                break
            part = m.group(2)
        if part and not _DROPPED.match(part):
            keep.append(part)
    return "/".join(keep) or "(no scope)"


def _program_of(module_event_name: str) -> str:
    m = re.match(r"(?:jit_|pmap_)?(.*?)(?:\(\d+\))?$", module_event_name)
    return m.group(1)


def _self_times(events):
    """``(start, end, key)`` of one line where a container (``while``) holds
    its children: yields ``(key, seconds not covered by nested events)``."""
    stack = []
    for a, b, key in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and a >= stack[-1][1]:
            s = stack.pop()
            yield s[2], (s[1] - s[0]) - s[3]
        if stack:
            stack[-1][3] += min(b, stack[-1][1]) - a
        stack.append([a, b, key, 0.0])
    while stack:
        s = stack.pop()
        yield s[2], (s[1] - s[0]) - s[3]


def by_scope(path: str, key=scope_of) -> Dict[str, dict]:
    """``{program: {"executions": n, "seconds": device seconds of its
    executions, "scopes": {scope: self seconds}, "kernels": {name: self
    seconds}}}`` summed over the device planes of the trace at ``path``.
    ``kernels`` are the custom calls (a pallas kernel's ``name=``), which
    also count under their scope. ``key`` maps an operation's ``tf_op`` to
    its row: ``op_path`` keeps the whole path."""
    out: Dict[str, dict] = defaultdict(
        lambda: {"executions": 0, "seconds": 0.0,
                 "scopes": defaultdict(float), "kernels": defaultdict(float)})
    for plane in read_planes(find_xplane(path)):
        if not plane.name.startswith("/device:"):
            continue
        mods = sorted((a, b, _program_of(plane.event_names.get(mid, "?")))
                      for a, b, mid in plane.lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        for a, b, prog in mods:
            out[prog]["executions"] += 1
            out[prog]["seconds"] += b - a
        keyed = []
        for a, b, mid in plane.lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            keyed.append((a, b, (prog, mid)))
        for (prog, mid), s in _self_times(keyed):
            ent = out[prog]
            # %flash_attention_fwd.3 = ... custom-call(...) -> the name
            name = plane.event_names.get(mid, "")
            op = re.sub(r"(\.\d+|\.remat\d*)+$", "",
                        name.split(" = ")[0].lstrip("%"))
            tf_op = plane.event_stats.get(mid, {}).get("tf_op")
            # XLA keeps no metadata on some fusions it merges (the
            # multi-output gather of K and V): say what it called them
            row = key(tf_op) if tf_op else f"(no scope) {op}"
            ent["scopes"][row] += s
            if " custom-call(" in name and not op.startswith("custom-call"):
                ent["kernels"][op] += s
    out = dict(out)
    for ent in out.values():
        ent["scopes"] = dict(ent["scopes"])
        ent["kernels"] = dict(ent["kernels"])
    return out


def format_table(table: Dict[str, dict], top: int = 12) -> str:
    lines = []
    for prog, ent in sorted(table.items(), key=lambda kv: -kv[1]["seconds"]):
        n = max(ent["executions"], 1)
        lines.append(f"{prog}: {ent['executions']} executions, "
                     f"{ent['seconds']:.4f} s on the device, "
                     f"{1e3 * ent['seconds'] / n:.3f} ms each")
        total = sum(ent["scopes"].values()) or 1.0
        for scope, s in sorted(ent["scopes"].items(),
                               key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {scope:<40}{1e3 * s / n:>10.3f} ms/exec"
                         f"{100 * s / total:>7.1f}%")
        for name, s in sorted(ent["kernels"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  kernel {name:<33}{1e3 * s / n:>10.3f} ms/exec")
    return "\n".join(lines)
