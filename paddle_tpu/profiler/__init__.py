"""paddle_tpu.profiler — tracing/profiling facade.

Reference being replaced (SURVEY.md §5):
- ``paddle.profiler.Profiler`` with scheduler states
  (python/paddle/profiler/profiler.py:271, ProfilerState :34);
- C++ Profiler composing HostTracer + CudaTracer into an event tree
  exported by ChromeTracingLogger (paddle/fluid/platform/profiler/*);
- ``RecordEvent`` host annotations (platform/profiler/event_tracing.h)
  sprinkled through the runtime (e.g. executor.cc:475);
- runtime counters StatRegistry/STAT_ADD (platform/monitor.h:80/133).

TPU-native design: device-side tracing is jax.profiler/XProf — the
captured trace (TensorBoard `plugins/profile` format) already contains
XLA op timelines, memory viewer, and roofline; ``RecordEvent`` is a
leaf phase of the observability span table (``tracing.phase``), which
opens a ``jax.profiler.TraceAnnotation`` so host annotations appear on
the same timeline. What the facade adds: Paddle-shaped scheduling
(wait/warmup/active cycles), host-side wall-clock aggregation for a
``summary()`` table without needing the XProf UI, and a StatRegistry for
counters.
"""

from __future__ import annotations

import collections
import contextlib
import enum
import os
import threading
import time
from typing import Callable, Dict, Iterable, Optional

import jax

from ..observability import tracing as _tracing


class ProfilerTarget(enum.Enum):
    """ref: profiler/profiler.py ProfilerTarget.CPU/GPU — here HOST/TPU."""
    HOST = 0
    TPU = 1


class SortedKeys(enum.Enum):
    """Sort keys for summary tables (ref: profiler_statistic.py
    SortedKeys — the CPU* family; device time lives in XProf)."""
    CPUTotal = "total"
    CPUAvg = "avg"
    CPUMax = "max"
    Calls = "calls"


class ProfilerState(enum.Enum):
    """ref: profiler/profiler.py:34 ProfilerState."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0
                   ) -> Callable[[int], ProfilerState]:
    """ref: paddle.profiler.make_scheduler — step-phase cycling."""
    period = closed + ready + record

    def sched(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        phase = s % period
        if phase < closed:
            return ProfilerState.CLOSED
        if phase < closed + ready:
            return ProfilerState.READY
        if phase == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return sched


# ---------------------------------------------------------------------------
# host event aggregation (the summary() table)
# ---------------------------------------------------------------------------

class _HostEvents:
    """Process-wide per-name durations behind ``summary()``. Every
    span that ends while a Profiler is started (``active``) lands here
    through ``record_stat`` — ``RecordEvent``s among them, and spans
    from worker threads (data loading, async checkpointing). The
    timeline itself is the span table's
    (``observability.export_chrome_tracing``)."""

    def __init__(self):
        self.stats: Dict[str, list] = collections.defaultdict(list)
        self.active = False
        self.lock = threading.Lock()

    def record_stat(self, name: str, dt: float) -> None:
        with self.lock:
            self.stats[name].append(dt)


_events = _HostEvents()

# which Profiler instance last start()ed: stop() only deactivates the
# shared event stream if it still owns it, so a stale stop (e.g. the
# debug server's timed /profilez disarm racing a job profiler started
# after it) can't silently kill the newer profiler's recording
_active_owner: Optional["Profiler"] = None


class RecordEvent:
    """Host-side annotation (ref: paddle.profiler.RecordEvent /
    platform RecordEvent): ``tracing.phase`` under the reference's
    name and begin/end protocol. While a profiler records it shows up
    in the XProf timeline, the span table and ``summary()``; otherwise
    it is a no-op."""

    def __init__(self, name: str):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _tracing.phase(self.name).__enter__()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


class Profiler:
    """ref: python/paddle/profiler/profiler.py:271.

    Usage::
        prof = Profiler(targets=[ProfilerTarget.TPU],
                        scheduler=make_scheduler(closed=1, ready=1,
                                                 record=3),
                        log_dir="./prof")
        prof.start()
        for step in ...:
            ...
            prof.step()
        prof.stop()
        print(prof.summary())
    """

    def __init__(self, targets: Optional[Iterable] = None,
                 scheduler: Optional[Callable] = None,
                 log_dir: str = "./paddle_tpu_profile",
                 on_trace_ready: Optional[Callable] = None):
        self.targets = list(targets or [ProfilerTarget.TPU])
        self.scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        self.log_dir = log_dir
        self.on_trace_ready = on_trace_ready
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        # [start, end] perf_counter pairs, one per RECORD window (end
        # None while the window is open) — export_chrome_tracing's
        # per-profiler filter renders only events inside these
        self._windows: list = []

    def recording_windows(self):
        """(start, end) perf_counter pairs of this profiler's RECORD
        phases; an open window reads as end=+inf."""
        import math
        return [(s, e if e is not None else math.inf)
                for s, e in self._windows]

    # -- device trace control -------------------------------------------
    def _start_trace(self):
        if not self._tracing:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
            self._tracing = True
            self._windows.append([time.perf_counter(), None])

    def _stop_trace(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self._windows and self._windows[-1][1] is None:
                self._windows[-1][1] = time.perf_counter()
            if self.on_trace_ready:
                self.on_trace_ready(self)

    # -- lifecycle ------------------------------------------------------
    def start(self):
        # clear UNDER the lock: worker threads may be inside
        # RecordEvent.end() → _events.record_stat() concurrently, and a
        # bare clear() races their defaultdict append (lost events /
        # dict-mutated-during-iteration in summary)
        with _events.lock:
            _events.stats.clear()
        self._windows = []
        _events.active = True
        global _active_owner
        _active_owner = self
        self._transition(self.scheduler(self.step_num))

    def step(self):
        self.step_num += 1
        self._transition(self.scheduler(self.step_num))

    def stop(self):
        global _active_owner
        self._stop_trace()
        self._state = ProfilerState.CLOSED
        if _active_owner is self or _active_owner is None:
            _events.active = False
            _active_owner = None

    def _transition(self, new_state: ProfilerState):
        # RECORD_AND_RETURN marks a cycle boundary: the trace closes (and
        # on_trace_ready fires) even if the next state records again
        if self._state == ProfilerState.RECORD_AND_RETURN:
            self._stop_trace()
        if new_state in (ProfilerState.RECORD,
                         ProfilerState.RECORD_AND_RETURN):
            self._start_trace()
        elif self._state == ProfilerState.RECORD:
            self._stop_trace()
        self._state = new_state

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- host-side stats (ref: profiler/profiler_statistic.py tables) ----
    def summary(self, sorted_by="total") -> str:
        """Statistic report (ref: profiler_statistic.py SummaryView):
        a model-perspective table (Dataloader / TrainStep / Callbacks
        buckets from ``Model.fit``'s ``fit.*`` phases, with time
        ratios) followed by the full host-event table. Device-side
        kernel timelines live in the XProf trace under ``log_dir``
        (view with xprof/tensorboard); the host tables cover what the
        reference's CPU-time columns did."""
        if isinstance(sorted_by, SortedKeys):
            sorted_by = sorted_by.value
        with _events.lock:
            snapshot = {k: list(v) for k, v in _events.stats.items()}
        rows = [(name, len(t), sum(t), sum(t) / len(t), max(t))
                for name, t in snapshot.items()]
        key = {"total": 2, "avg": 3, "max": 4, "calls": 1}[sorted_by]
        rows.sort(key=lambda r: -r[key])

        def table(title, rs, extra_ratio_of=None):
            lines = [title,
                     f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}"
                     f"{'Avg(s)':>12}{'Max(s)':>12}" +
                     (f"{'Ratio':>9}" if extra_ratio_of else "")]
            for name, calls, total, avg, mx in rs:
                line = (f"{name[:39]:<40}{calls:>8}{total:>12.6f}"
                        f"{avg:>12.6f}{mx:>12.6f}")
                if extra_ratio_of:
                    line += f"{100.0 * total / extra_ratio_of:>8.1f}%"
                lines.append(line)
            return lines

        out = []
        perspective = [(_MODEL_PERSPECTIVE[r[0]],) + r[1:] for r in rows
                       if r[0] in _MODEL_PERSPECTIVE]
        if perspective:
            wall = sum(r[2] for r in perspective)
            out += table("---- Model Perspective "
                         "(ref: model summary table) ----",
                         perspective, extra_ratio_of=wall)
            out.append("")
        out += table("---- Host Events ----", rows)
        return "\n".join(out)


# summary()'s model-perspective rows: Model.fit's phases under the
# reference's bucket names
_MODEL_PERSPECTIVE = {"fit.next_batch": "Dataloader",
                      "fit.dispatch": "TrainStep",
                      "fit.callbacks": "Callbacks", "fit.eval": "Eval"}


@contextlib.contextmanager
def profile(log_dir: str = "./paddle_tpu_profile"):
    """One-shot trace context (jax.profiler.trace with the Paddle name)."""
    p = Profiler(log_dir=log_dir)
    p.start()
    try:
        yield p
    finally:
        p.stop()


# Host-annotation chrome://tracing export (ref: ChromeTracingLogger).
# Device-side timelines remain in the XProf dump under log_dir
# (`tensorboard --logdir <log_dir>` or xprof); this file carries the
# spans (RecordEvents among them) the summary() table aggregates.
from ..observability.exporters import export_chrome_tracing  # noqa: E402,F401
