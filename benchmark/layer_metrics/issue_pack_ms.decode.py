"""Scheduler: Python planning and packing of a decode dispatch, median in ms:
from the start of ``llm.issue.decode`` to its ``packed`` mark, where the
plan and the host arrays are complete (the slot loop, ``positions`` and
``lens``). None without marks."""
from benchmark.layer_metrics import _marks


def compute(spans):
    return _marks.median(
        _marks.between_ms(spans, "decode", "start", "packed"))


def read(facts, trace):
    return compute(_marks.finished())
