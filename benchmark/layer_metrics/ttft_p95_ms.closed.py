"""Scheduler and front, closed loop: 95th percentile over the requests
answered in the window of the time to first token as the client is owed it
(response ``ttft_s`` plus the front's share of the round trip). A per-layer
metric in a closed loop: the callers saturate the engine, and a tail over some
tens of requests swings by more than a bound may be wide."""
from benchmark import stats


def read(facts, trace):
    xs = facts.get("samples", {}).get("ttft_ms")
    return stats.percentile(xs, 95) if xs else None
