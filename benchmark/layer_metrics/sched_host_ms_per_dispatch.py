"""Scheduler: host milliseconds the engine thread works for each dispatch,
from the program's span table: the summed durations of its ``llm.loop.*``
(not ``llm.loop.idle``), ``llm.issue.*`` and ``llm.drain.emit`` phases over
the count of ``llm.issue.*``. ``llm.drain.wait`` (blocked on the device) and
the idle wait are left out: what remains is what the device waits for when a
tick drains to its boundary. With one dispatch in the table it is that
dispatch's own sum; with none, None."""
from benchmark.layer_metrics import _spans


def compute(spans):
    work = [s for s in _spans.named(spans, _spans.ENGINE_WORK)
            if s["name"] != "llm.loop.idle"]
    issues = _spans.named(work, "llm.issue.")
    if not issues:
        return None
    return sum(s["dur"] for s in work) * 1e3 / len(issues)


def read(facts, trace):
    return compute(_spans.finished())
