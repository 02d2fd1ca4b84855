"""Engine programs: bytes the attention path took out of the K/V pool in the
traced dispatches (the ``kv_pages_read`` attr of the ``llm.issue.*`` phases
times the bytes of a page over all its cache layers) over the tokens they
produced (the ``tokens`` attr of the ``llm.drain.emit`` phases): what 192
cache layers cost a token at this batch. None with no such attrs in the
table, or a system that does not say what a page holds."""
from benchmark.layer_metrics import _spans


def compute(spans, page_bytes):
    pages = sum(s.get("attrs", {}).get("kv_pages_read", 0)
                for s in _spans.named(spans, "llm.issue."))
    tokens = sum(s.get("attrs", {}).get("tokens", 0)
                 for s in _spans.named(spans, "llm.drain.emit"))
    if not pages or not tokens or not page_bytes:
        return None
    return pages * page_bytes / tokens


def read(facts, trace):
    return compute(_spans.finished(), facts.get("page_bytes"))
