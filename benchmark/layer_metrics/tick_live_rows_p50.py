"""Scheduler: rows that can emit a token in one dispatch (the serving sheet's
"batch size per step"): the median ``live_rows`` attr over the engine's
``llm.issue.*`` phases. Tokens a second are these rows over tick time, so 32
callers that keep fewer than 32 rows live show here. With one dispatch in the
table it is that dispatch's rows; with none, None."""
from benchmark import stats
from benchmark.layer_metrics import _spans


def compute(spans):
    rows = [s["attrs"]["live_rows"]
            for s in _spans.named(spans, "llm.issue.")
            if "live_rows" in s.get("attrs", {})]
    return stats.percentile(rows, 50) if rows else None


def read(facts, trace):
    return compute(_spans.finished())
