"""Scheduler: the host's turn before a decode dispatch, median in ms: the
``launched`` mark of each ``llm.issue.decode`` less the end of the latest
``llm.drain.wait`` before it (``_marks.host_turns_ms``: emit, the loop's
head, control, admit, plan, pack, stage and the jitted call; a pair with an
``llm.loop.idle`` between is left out). What the device waits for that is
the program's own, by kind of dispatch, where ``sched_host_ms_per_dispatch``
is a mean over both kinds of whole phases, the part after the launch
included. None without marks; one dispatch gives its own turn."""
from benchmark.layer_metrics import _marks


def compute(spans):
    return _marks.median(_marks.host_turns_ms(spans, "decode"))


def read(facts, trace):
    return compute(_marks.finished())
