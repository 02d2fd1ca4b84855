"""Scheduler: how long an admitted prompt waits in the prefill queue for its
first chunk: from the start of the request's ``llm.prefill`` span (admission)
to its first ``chunk`` event (stamped with the start of the issue phase that
carried it), MEAN over the requests rooted in the traced window whose prompt
got a chunk. After ``sched_queue_wait_p50_ms`` (submit to admission) this is
the second part of TTFT. A mean and not a median because the wait is
two-valued: a prompt admitted with under one chunk of prompt tokens ahead of
it is served in the same loop iteration (0.05 ms), any other waits whole mixed
ticks, and the median of some 17 requests jumps from one side to the other
(0.04 or 398 ms, PR 24) where the mean follows the share that waited. With a
few requests it is the mean of those few (of one: its own wait); with none,
None."""
from benchmark.layer_metrics import _spans


def compute(spans):
    waits = [first - start
             for start, first in _spans.first_chunks(spans).values()]
    return sum(waits) / len(waits) * 1e3 if waits else None


def read(facts, trace):
    return compute(_spans.finished())
