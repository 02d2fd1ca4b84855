"""Scheduler: the jitted call of a decode dispatch, median in ms: from the
``staged`` mark of ``llm.issue.decode`` to its ``launched`` mark (argument
flattening over the parameter tree and the runtime's enqueue; the call
returns before the device has finished). None without marks."""
from benchmark.layer_metrics import _marks


def compute(spans):
    return _marks.median(
        _marks.between_ms(spans, "decode", "staged", "launched"))


def read(facts, trace):
    return compute(_marks.finished())
