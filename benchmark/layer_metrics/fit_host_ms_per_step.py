"""Trainer: host milliseconds ``Model.fit`` spends fetching a batch and
dispatching a step, from the program's span table: the summed durations of
its ``fit.next_batch`` and ``fit.dispatch`` phases over the optimizer steps
dispatched (``fit.dispatch`` count x steps an execution). Callbacks, where a
caller's loss fetch waits for the device, are left out. With one dispatch in
the table it is that step's own sum; with none, None."""
from benchmark.layer_metrics import _spans


def compute(spans, steps_per_execution=1):
    dispatches = _spans.named(spans, "fit.dispatch")
    if not dispatches:
        return None
    host = dispatches + _spans.named(spans, "fit.next_batch")
    return sum(s["dur"] for s in host) * 1e3 \
        / (len(dispatches) * steps_per_execution)


def read(facts, trace):
    return compute(_spans.finished(),
                   int(facts.get("steps_per_execution", 1)))
