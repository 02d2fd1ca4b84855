"""Engine programs: pages the attention path reads from the KV pool for each
distinct live page of the sequences it serves: the sum of the
``kv_pages_read`` attr over the sum of the ``kv_pages_live`` attr of the
engine's ``llm.issue.*`` phases. The engine computes both on the host from
the limits it packs: the paged-attention kernel reads a row's
``ceil(limit / page_size)`` pages, the gathered path every entry of every
row's table; live is each sequence's longest limit in pages, once. 1.0 is a
decode tick through the kernel; above it, rows of one sequence (a prompt's
chunk) each read that sequence's pages again. With no such phase in the
table, or a program that does not stamp the attrs (the parent of the PR that
added them), None."""
from benchmark.layer_metrics import _spans


def compute(spans):
    issues = [s.get("attrs", {}) for s in _spans.named(spans, "llm.issue.")]
    live = sum(a.get("kv_pages_live", 0) for a in issues)
    if not live:
        return None
    return sum(a.get("kv_pages_read", 0) for a in issues) / live


def read(facts, trace):
    return compute(_spans.finished())
