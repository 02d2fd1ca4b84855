"""Scheduler: what is left of the device's idle time a dispatch when the
program's own turn is taken out, in ms: (``window_s`` - ``busy_s``) of the
reduced trace over the executions it holds of ``mixed_fn``, ``decode_fn`` and
``slab_fn``, less the MEAN host turn over both kinds of dispatch from the span
table (``_marks.host_turns_ms``). The device's side is a total over a count,
so it can only be a mean, and the host's side is a mean to match; the by-kind
``host_turn_ms.*`` are medians. The spans are recorded exactly while the
profiler session runs, so both terms cover the same window. What remains is
the fetch reaching the host after the program's last operation, the launch
reaching the device, and the gaps under 50 us between a program's operations:
nothing the engine's Python can shorten; the lever on it is to have the next
program queued before this one ends. None without a device trace or marks."""
from benchmark.layer_metrics import _marks

PROGRAMS = ("mixed_fn", "decode_fn", "slab_fn")


def compute(spans, trace):
    if trace is None or not trace["devices"] or trace["window_s"] <= 0:
        return None
    runs = sum(len(trace["programs"].get(p, ())) for p in PROGRAMS)
    turns = _marks.host_turns_ms(spans)
    if not runs or not turns:
        return None
    idle_ms = (trace["window_s"] - trace["busy_s"]) * 1e3 / runs
    return idle_ms - sum(turns) / len(turns)


def read(facts, trace):
    return compute(_marks.finished(), trace)
