"""Scheduler (the page pool): pool bytes the live slots hold, summed over
the cache groups, over the context tokens they stand for: the sums of
``kv_groups[*].bytes_held`` and of ``context_tokens`` over the engine's
``llm.issue.*`` phases. With every cache layer on one lifetime a token costs
its bytes in every layer for as long as its sequence lives (49,152 B at
twelve layers of 8 K/V heads of 128 in bf16); a window group frees what lies
behind the window, so a long context costs less a token. None with no such
attrs in the table (a program whose pool is one group, or no traced run)."""
from benchmark.layer_metrics import _spans


def compute(spans):
    held = tokens = 0
    for s in _spans.named(spans, "llm.issue."):
        a = s.get("attrs", {})
        if "kv_groups" not in a or not a.get("context_tokens"):
            continue
        held += sum(g["bytes_held"] for g in a["kv_groups"].values())
        tokens += a["context_tokens"]
    return held / tokens if tokens else None


def read(facts, trace):
    return compute(_spans.finished())
