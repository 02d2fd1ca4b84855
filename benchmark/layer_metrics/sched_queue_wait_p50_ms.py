"""Scheduler: median of ``llm_queue_wait_seconds`` (submit to admission) over
the window, from the histogram's buckets."""
from benchmark import stats


def read(facts, trace):
    b, a = facts.get("before", {}), facts.get("after", {})
    if not b.get("queue_wait") or not a.get("queue_wait"):
        return None
    q = stats.histogram_quantile(b["queue_wait"], a["queue_wait"], 0.5)
    return None if q is None else q * 1e3
