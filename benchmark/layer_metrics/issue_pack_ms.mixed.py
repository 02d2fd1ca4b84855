"""Scheduler: Python planning and packing of a mixed dispatch, median in ms:
from the start of ``llm.issue.mixed`` to its ``packed`` mark, where the plan
and the host arrays are complete (``_plan_slab``, the chunk loop with its
store a prompt token, ``ensure_range``). None without marks."""
from benchmark.layer_metrics import _marks


def compute(spans):
    return _marks.median(
        _marks.between_ms(spans, "mixed", "start", "packed"))


def read(facts, trace):
    return compute(_marks.finished())
