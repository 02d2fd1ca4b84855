"""Engine programs: from a prompt's first ``chunk`` event to the end of its
``llm.first_token`` span (the fetch that delivered the token): the chunks
themselves, the ticks between them and the drain, median over the requests
rooted in the traced window whose first token has arrived. The last part of
TTFT after ``sched_queue_wait_p50_ms`` and ``prefill_wait_mean_ms``. With a
few requests it is the median of those few; with none, None."""
from benchmark.layer_metrics import _spans


def compute(spans):
    chunks = _spans.first_chunks(spans)
    spent = [s["ts"] + s["dur"] - chunks[s["parent_id"]][1]
             for s in _spans.named(spans, "llm.first_token")
             if s["parent_id"] in chunks]
    return _spans.median_ms(spent) if spent else None


def read(facts, trace):
    return compute(_spans.finished())
