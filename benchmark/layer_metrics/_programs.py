"""Shared by the readers of time per program: the median device duration of
one execution of the first of ``names`` the trace holds."""
from benchmark import stats


def median_ms(trace, names):
    if trace is None:
        return None
    for name in names:
        xs = trace["programs"].get(name)
        if xs:
            return stats.percentile(xs, 50) * 1e3
    return None


def idle_share(trace):
    if trace is None or not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
