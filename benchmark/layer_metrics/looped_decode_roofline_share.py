"""Engine programs: the least time a traced decode tick of the looped
configuration could take on the chip's memory bandwidth, over the median
device time of one ``decode_fn`` execution, in percent. The floor's bytes are
``roofline_looped.decode_tick_bytes`` of what each traced
``llm.issue.decode`` phase says it served (``kv_pages_live``; ``loop_steps``
and ``kv_cache_layers`` must be the configuration's): the median over the
traced ticks. A share of a floor: it cannot pass 100. None where the trace
holds no ``decode_fn``, the span table no such attrs (a program that runs
its stack once), or the peaks are unknown (a rehearsal)."""
from benchmark import roofline_looped, stats
from benchmark.layer_metrics import _programs, _spans


def tick_bytes(spans, dims, page_size):
    out = []
    for s in _spans.named(spans, "llm.issue.decode"):
        a = s.get("attrs", {})
        if a.get("loop_steps") != dims["steps"] or a.get(
                "kv_cache_layers") != roofline_looped.cache_layers(dims):
            continue
        out.append(roofline_looped.decode_tick_bytes(
            dims, a.get("kv_pages_live", 0), page_size))
    return out


def compute(spans, dims, page_size, tick_ms, bytes_per_s):
    ticks = tick_bytes(spans, dims, page_size)
    if not ticks or not tick_ms:
        return None
    floor_ms = stats.percentile(ticks, 50) / bytes_per_s * 1e3
    return 100.0 * floor_ms / tick_ms


def read(facts, trace):
    dims, peaks = facts.get("dims"), facts.get("peaks")
    if not dims or "steps" not in dims or not peaks:
        return None
    ms = _programs.median_ms(trace, ("decode_fn",))
    return compute(_spans.finished(), dims, facts["page_size"], ms,
                   peaks["hbm_bytes_per_s"])
