"""Experts: over the window, the busiest held expert's rows over the mean
held expert's rows, in the worst layer: 1.0 is an even routing; the grouped
expert product waits for its longest group. From the engine's per-layer
per-expert row counts (``moe_rows_by_expert``, what ``/statusz`` shows), after
minus before. None for a program without routed experts."""


def compute(before, after):
    worst = None
    for b, a in zip(before, after):
        rows = [y - x for x, y in zip(b, a)]
        if sum(rows) <= 0:
            continue
        ratio = max(rows) * len(rows) / sum(rows)
        worst = ratio if worst is None else max(worst, ratio)
    return worst


def read(facts, trace):
    b = (facts.get("before") or {}).get("moe_rows_by_expert")
    a = (facts.get("after") or {}).get("moe_rows_by_expert")
    if not a or not b:
        return None
    return compute(b, a)
