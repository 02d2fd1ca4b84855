"""Scheduler: host-to-device staging of a mixed dispatch, median in ms: from
the ``packed`` mark of ``llm.issue.mixed`` to its ``staged`` mark, where
every argument of the program is a device array (the chunk schedule's
``jnp.asarray`` calls, the rows' block tables, the carry, temperatures and
nonces). None without marks."""
from benchmark.layer_metrics import _marks


def compute(spans):
    return _marks.median(
        _marks.between_ms(spans, "mixed", "packed", "staged"))


def read(facts, trace):
    return compute(_marks.finished())
