"""Scheduler: host-to-device staging of a decode dispatch, median in ms: from
the ``packed`` mark of ``llm.issue.decode`` to its ``staged`` mark, where
every argument of the program is a device array (positions, limits, the
block tables, temperatures and nonces). None without marks."""
from benchmark.layer_metrics import _marks


def compute(spans):
    return _marks.median(
        _marks.between_ms(spans, "decode", "packed", "staged"))


def read(facts, trace):
    return compute(_marks.finished())
