"""Scheduler: finished spans the program's span table evicted during the
traced run (``tracing.dropped_spans()``: the table is a ring). Anything but 0
means every ``program_span`` reader saw a shorter window than the device trace
beside it. None for a program without the count (the parent commit)."""


def read(facts, trace):
    from paddle_tpu.observability import tracing
    count = getattr(tracing, "dropped_spans", None)
    return None if count is None else count()
