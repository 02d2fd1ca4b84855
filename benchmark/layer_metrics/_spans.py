"""Shared by the readers of the program's own span table
(``paddle_tpu.observability.tracing.finished_spans()``): a list of dicts with
``name``, ``ts`` and ``dur`` in seconds on ``perf_counter``, ``parent_id``,
``attrs`` and ``events``. The program records spans while a profiler session
runs, so after a traced run the table holds the traced window and nothing
else; after any other run, and with a program that has no such spans, it is
empty and every reader returns None."""
from benchmark import stats

ENGINE_WORK = ("llm.loop.", "llm.issue.", "llm.drain.emit")


def finished() -> list:
    from paddle_tpu.observability import tracing
    return [s for s in tracing.finished_spans() if s.get("dur") is not None]


def named(spans, prefixes) -> list:
    return [s for s in spans if s["name"].startswith(prefixes)]


def median_ms(seconds) -> float:
    return stats.percentile(seconds, 50) * 1e3


def first_chunks(spans) -> dict:
    """``{request root's span id: (llm.prefill start, its first chunk
    event's time)}`` for every finished ``llm.prefill`` that has a chunk."""
    out = {}
    for s in named(spans, "llm.prefill"):
        chunks = [e["ts"] for e in s.get("events", ())
                  if e["name"] == "chunk"]
        if chunks:
            out[s["parent_id"]] = (s["ts"], min(chunks))
    return out
