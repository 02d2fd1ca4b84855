"""Scheduler: recurrent-state bytes the traced dispatches read and wrote
(the ``state_bytes`` attr of the ``llm.issue.*`` phases) over the tokens they
produced (the ``tokens`` attr of the ``llm.drain.emit`` phases). A decode
tick steps every slot's state row whatever is live, so a fuller batch brings
it down, as does a prompt chunk that carries its state once for many prompt
tokens' worth of work. None with no such attr in the table (a program
without recurrent state: every ``state_bytes`` is 0)."""
from benchmark.layer_metrics import _spans


def compute(spans):
    moved = sum(s.get("attrs", {}).get("state_bytes", 0)
                for s in _spans.named(spans, "llm.issue."))
    tokens = sum(s.get("attrs", {}).get("tokens", 0)
                 for s in _spans.named(spans, "llm.drain.emit"))
    if not moved or not tokens:
        return None
    return moved / tokens


def read(facts, trace):
    return compute(_spans.finished())
