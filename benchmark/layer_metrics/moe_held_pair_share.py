"""Experts: of the (row, expert) pairs the router made in the window, the
share that fell on the experts this chip holds, in percent
(``llm_moe_rows_routed_total{held}``: held over held + absent). ~50 for half
of the experts under an even router: the chip's share of the deployment is
the share of the work. None for a program without routed experts."""


def compute(before, after):
    pairs = after["moe_pairs"] - before["moe_pairs"]
    if pairs <= 0:
        return None
    return 100.0 * (after["moe_pairs_held"] - before["moe_pairs_held"]) \
        / pairs


def read(facts, trace):
    b, a = facts.get("before") or {}, facts.get("after") or {}
    if "moe_pairs" not in a or "moe_pairs" not in b:
        return None
    return compute(b, a)
