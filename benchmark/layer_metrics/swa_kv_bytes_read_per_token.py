"""Engine programs: bytes the attention path took out of the K/V pools in the
traced dispatches (by cache group, the ``read`` pages of ``kv_groups`` on the
``llm.issue.*`` phases times that group's ``page_bytes``) over the tokens
they produced (the ``tokens`` attr of the ``llm.drain.emit`` phases). A
prompt's chunk rows walk their sequence's pages row by row, so a chunk of 256
rows over a 4k context reads the full group's pages 256 times: that, beside
the decode rows' one walk a tick, is what a token costs here. None with no
such attrs in the table (a program whose pool is one group)."""
from benchmark.layer_metrics import _spans


def compute(spans):
    read = sum(g["read"] * g["page_bytes"]
               for s in _spans.named(spans, "llm.issue.")
               for g in s.get("attrs", {}).get("kv_groups", {}).values())
    tokens = sum(s.get("attrs", {}).get("tokens", 0)
                 for s in _spans.named(spans, "llm.drain.emit"))
    if not read or not tokens:
        return None
    return read / tokens


def read(facts, trace):
    return compute(_spans.finished())
