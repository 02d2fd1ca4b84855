"""Engine programs: the least time a traced decode tick of the sink /
unequal-widths configuration could take on the chip's memory bandwidth, over
the median device time of one ``decode_fn`` execution, in percent. The
floor's bytes are ``roofline_mimo.decode_tick_bytes`` of what each traced
``llm.issue.decode`` phase says it served, by cache group (``kv_groups``: the
``full`` group's live pages, the ``window`` group's pages inside a live row's
window, each at its group's K and V bytes at the PUBLISHED widths: a key
192, not the 256 it is stored in) and what the
``llm.drain.emit`` phase of the same ``issue_seq`` says the routing touched
(``experts_touched``): the median over the traced ticks, whose four terms are
printed on a line of their own, the two page terms AS STORED beside them.
A share of a floor: it cannot pass 100. A
share of ONE kernel's roofline still waits for ``trace_reduce`` to hand
readers operation times (ROADMAP A0b(e)). None where the trace holds no
``decode_fn``, the span table no such attrs (a program whose groups do not
say ``k_row_bytes``: the parent), or the peaks are unknown (a rehearsal)."""
import json

from benchmark import roofline_mimo, stats
from benchmark.layer_metrics import _programs, _spans


def ticks_served(spans):
    """``(experts touched, full pages, window pages)`` of every traced
    decode tick that says all three."""
    touched = {s["attrs"]["issue_seq"]: s["attrs"]["experts_touched"]
               for s in _spans.named(spans, "llm.drain.emit")
               if "experts_touched" in s.get("attrs", {})}
    out = []
    for s in _spans.named(spans, "llm.issue.decode"):
        a = s.get("attrs", {})
        groups = a.get("kv_groups") or {}
        if set(groups) != {"full", "window"} \
                or "k_row_bytes" not in groups["window"] \
                or a.get("issue_seq") not in touched:
            continue
        out.append((touched[a["issue_seq"]], groups["full"]["live"],
                    groups["window"]["live"]))
    return out


def compute(spans, dims, page_size, tick_ms, bytes_per_s, say=None):
    def floor(served):
        return roofline_mimo.decode_tick_bytes(dims, *served, page_size)

    ticks = sorted(ticks_served(spans), key=floor)
    if not ticks or not tick_ms:
        return None
    floor_bytes = stats.percentile([floor(t) for t in ticks], 50)
    floor_ms = floor_bytes / bytes_per_s * 1e3
    median = ticks[len(ticks) // 2]
    if say is not None:
        say({"sink_decode_roofline_share": {
            "traced_decode_ticks": len(ticks),
            "median_tick_terms_bytes": roofline_mimo.decode_tick_terms(
                dims, *median, page_size),
            "median_tick_pages_bytes_as_stored":
            roofline_mimo.stored_page_terms(dims, *median[1:], page_size),
            "floor_bytes": floor_bytes, "floor_ms": floor_ms,
            "decode_fn_median_ms": tick_ms,
            "hbm_bytes_per_s": bytes_per_s}})
    return 100.0 * floor_ms / tick_ms


def read(facts, trace):
    dims, peaks = facts.get("dims"), facts.get("peaks")
    if not dims or "swa_sink" not in dims or not peaks:
        return None
    ms = _programs.median_ms(trace, ("decode_fn",))
    return compute(_spans.finished(), dims, facts["page_size"], ms,
                   peaks["hbm_bytes_per_s"],
                   lambda obj: print(json.dumps(obj), flush=True))
