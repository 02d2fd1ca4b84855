"""Shared by the readers of the marks inside the engine's issue phases.

Since PR 37 every ``llm.issue.mixed`` and ``llm.issue.decode`` phase that
launches a program carries four events, in this order: ``packed`` (the plan
and the host arrays are complete), ``staged`` (every argument is a device
array), ``launched`` (the jitted call has returned: the device works from here
on), ``booked`` (the engine's own book-keeping after the launch is done; what
runs from there to the phase's end exists for the trace alone). The readers
cut a dispatch's host time at those marks, one kind of dispatch at a time: a
mixed dispatch packs a chunk of prompt rows and stages a dozen arrays, a decode
dispatch neither, and a mean over both moves with the mix.

All times are ``perf_counter`` seconds of the engine thread, as the span table
holds them. A table whose issue phases carry no marks (the parent commit, an
untraced run, a program without such spans) gives every reader None."""
from benchmark import stats
from benchmark.layer_metrics import _spans

KINDS = ("mixed", "decode")


def times(phase) -> dict:
    """``{event name: time}`` of one phase, its own ``start`` and ``end``
    among them."""
    at = {e["name"]: e["ts"] for e in phase.get("events", ())}
    at.update(start=phase["ts"], end=phase["ts"] + phase["dur"])
    return at


def between_ms(spans, kind, a, b) -> list:
    """Milliseconds from mark ``a`` to mark ``b`` (``start`` and ``end`` are
    the phase's own) of each ``llm.issue.<kind>`` that carries a ``launched``
    mark and both of them."""
    out = []
    for s in _spans.named(spans, "llm.issue." + kind):
        at = times(s)
        if "launched" in at and a in at and b in at:
            out.append((at[b] - at[a]) * 1e3)
    return out


def host_turns_ms(spans, kind=None) -> list:
    """The host's turns, in milliseconds: for each marked dispatch of ``kind``
    (both kinds for None) its ``launched`` mark less the END of the latest
    ``llm.drain.wait`` before the phase, which is where the engine thread had
    the last program's tokens and the device, with nothing queued, began to
    wait for the next launch. A pair with an ``llm.loop.idle`` between them
    is left out (the engine had nothing to do: not a turn), and so is one
    with another launch between them (the device was not waiting)."""
    out, since = [], None
    wanted = tuple("llm.issue." + k for k in ((kind,) if kind else KINDS))
    for s in sorted(_spans.named(spans, (
            "llm.drain.wait", "llm.loop.idle", "llm.issue.")),
            key=lambda s: s["ts"]):
        if s["name"] == "llm.drain.wait":
            since = s["ts"] + s["dur"]
        elif s["name"] == "llm.loop.idle":
            since = None
        else:
            launched = times(s).get("launched")
            if launched is None and "issue_seq" not in s.get("attrs", {}):
                continue    # found nothing to launch: the device waits on
            if None not in (launched, since) and s["name"] in wanted:
                out.append((launched - since) * 1e3)
            since = None
    return out


def median(ms):
    """Of a list of milliseconds; None of an empty one."""
    return stats.percentile(ms, 50) if ms else None


finished = _spans.finished
