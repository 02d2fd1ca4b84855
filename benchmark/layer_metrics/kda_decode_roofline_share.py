"""Engine programs: the least time a traced decode tick of the delta-rule /
latent-attention configuration could take on the chip's memory bandwidth,
over the median device time of one ``decode_fn`` execution, in percent. The
floor's bytes are ``roofline_kda.decode_tick_bytes`` of what each traced
``llm.issue.decode`` phase says it served (``state_rows``: the live rows
whose convolution tail and delta-rule state are read and written;
``kv_groups``: the latent group's live pages, at the bytes a row is stored
in) and what the ``llm.drain.emit`` phase of the same ``issue_seq`` says the
routing touched (``experts_touched``): the median over the traced ticks. A
share of a floor: it cannot pass 100. A share of ONE kernel's roofline still
waits for ``trace_reduce`` to hand readers operation times (ROADMAP A0b(e)).
None where the trace holds no ``decode_fn``, the span table no such attrs (a
program without a latent group or without state rows), or the peaks are
unknown (a rehearsal)."""
from benchmark import roofline_kda, stats
from benchmark.layer_metrics import _programs, _spans


def tick_bytes(spans, dims, page_size):
    touched = {s["attrs"]["issue_seq"]: s["attrs"]["experts_touched"]
               for s in _spans.named(spans, "llm.drain.emit")
               if "experts_touched" in s.get("attrs", {})}
    out = []
    for s in _spans.named(spans, "llm.issue.decode"):
        a = s.get("attrs", {})
        latent = (a.get("kv_groups") or {}).get("latent")
        if latent is None or "state_rows" not in a \
                or a.get("issue_seq") not in touched:
            continue
        out.append(roofline_kda.decode_tick_bytes(
            dims, touched[a["issue_seq"]], a["state_rows"], latent["live"],
            page_size))
    return out


def compute(spans, dims, page_size, tick_ms, bytes_per_s):
    ticks = tick_bytes(spans, dims, page_size)
    if not ticks or not tick_ms:
        return None
    floor_ms = stats.percentile(ticks, 50) / bytes_per_s * 1e3
    return 100.0 * floor_ms / tick_ms


def read(facts, trace):
    dims, peaks = facts.get("dims"), facts.get("peaks")
    if not dims or "latent_width" not in dims or not peaks:
        return None
    ms = _programs.median_ms(trace, ("decode_fn",))
    return compute(_spans.finished(), dims, facts["page_size"], ms,
                   peaks["hbm_bytes_per_s"])
