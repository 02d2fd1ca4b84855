"""Engine programs: the least time a traced decode tick of the
gated-delta-rule / full-attention configuration could take on the chip's
memory bandwidth, over the median device time of one ``decode_fn`` execution,
in percent. The floor's bytes are ``roofline_olmo.decode_tick_bytes`` of what
each traced ``llm.issue.decode`` phase says it served (``state_rows``: the
live rows whose convolution tail and delta-rule state are read and written,
at the PUBLISHED 96 x 192 a head; ``kv_groups``: the ``full`` group's live
pages, at the published thirty heads: 30,720 B a token); the weights are
read once whatever the rows: the median over the traced ticks. Its three
terms stand on a line of their own, with the bytes the program STORES for the
same rows and pages beside them (what the span attrs ``state_bytes`` and
``page_bytes`` say). A share of a floor: it cannot pass 100. None where the
trace holds no ``decode_fn``, the span table no such attrs (a program without
a ``full`` group or without state rows), or the peaks are unknown (a
rehearsal)."""
import json

from benchmark import roofline_olmo, stats
from benchmark.layer_metrics import _programs, _spans


def ticks(spans, dims, page_size):
    """``(floor terms, stored bytes)`` of every traced decode tick that says
    what it served."""
    out = []
    for s in _spans.named(spans, "llm.issue.decode"):
        a = s.get("attrs", {})
        full = (a.get("kv_groups") or {}).get("full")
        if full is None or "state_rows" not in a:
            continue
        terms = roofline_olmo.decode_tick_terms(
            dims, a["state_rows"], full["live"], page_size)
        stored = {"state": a.get("state_bytes"),
                  "pages": full["live"] * full.get("page_bytes", 0)}
        out.append((terms, stored))
    return out


def compute(spans, dims, page_size, tick_ms, bytes_per_s, say=None):
    served = ticks(spans, dims, page_size)
    if not served or not tick_ms:
        return None
    served.sort(key=lambda tick: sum(tick[0].values()))
    floor_bytes = stats.percentile([sum(t.values()) for t, _ in served], 50)
    floor_ms = floor_bytes / bytes_per_s * 1e3
    terms, stored = served[len(served) // 2]
    if say is not None:
        say({"gdn_decode_roofline_share": {
            "traced_decode_ticks": len(served),
            "median_tick_terms_bytes": terms,
            "median_tick_bytes_as_stored": stored,
            "floor_bytes": floor_bytes, "floor_ms": floor_ms,
            "decode_fn_median_ms": tick_ms,
            "hbm_bytes_per_s": bytes_per_s}})
    return 100.0 * floor_ms / tick_ms


def read(facts, trace):
    dims, peaks = facts.get("dims"), facts.get("peaks")
    if not dims or "lin_heads" not in dims or not peaks:
        return None
    ms = _programs.median_ms(trace, ("decode_fn",))
    return compute(_spans.finished(), dims, facts["page_size"], ms,
                   peaks["hbm_bytes_per_s"],
                   lambda obj: print(json.dumps(obj), flush=True))
