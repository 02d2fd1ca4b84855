"""Scheduler: what the trace's own arithmetic costs the engine thread, mean
ms a dispatch over both kinds: from the ``booked`` mark of each
``llm.issue.mixed`` and ``llm.issue.decode`` to the phase's end, where only
the ``set_attr`` calls, ``_stamp_state`` and ``_stamp_kv_pages`` run. It is
the instrument's share of ``sched_host_ms_per_dispatch``: a traced run pays
it (after the launch, so beside the device's work) and an untraced run does
not. None without marks."""
from benchmark.layer_metrics import _marks


def compute(spans):
    ms = [x for kind in _marks.KINDS
          for x in _marks.between_ms(spans, kind, "booked", "end")]
    return sum(ms) / len(ms) if ms else None


def read(facts, trace):
    return compute(_marks.finished())
