"""HTTP front: client round trip minus the response's ``latency_s``, median."""
from benchmark import stats


def read(facts, trace):
    xs = facts.get("samples", {}).get("front_overhead_ms")
    return stats.percentile(xs, 50) if xs else None
