"""From the load generator's records to the serving metrics: host arithmetic."""

from __future__ import annotations

from . import stats


def in_window(rec: dict, t0: float, t_end: float, by: str) -> bool:
    """``by`` is the traffic kind's ``WINDOW_BY``. An open-loop request belongs to the window it was due in (and is
    waited for after it closes); a closed-loop one to the window its reply
    arrived in: the loop is stationary, so the requests in flight when the
    window opens stand for those in flight when it closes."""
    return t0 <= rec[by] < t_end


def tokens_inside(rec: dict, t0: float, t_end: float) -> float:
    """Output tokens of one finished request that fell inside the window.
    ``serve_llm`` replies once, at the end, so the times of single tokens are
    not seen: the first token is placed ``latency_s - ttft_s`` before the
    reply and the others evenly between it and the reply."""
    n = len(rec["output_ids"])
    done = rec["done"]
    first = done - (rec["latency_s"] - rec["ttft_s"])
    inside = 1.0 if t0 <= first < t_end else 0.0
    if n > 1 and done > first:
        overlap = max(0.0, min(done, t_end) - max(first, t0))
        inside += (n - 1) * overlap / (done - first)
    return inside


def reduce(records: list, t0: float, t_end: float, by: str,
           unanswered: int) -> dict:
    """End-to-end numbers and the samples behind them.

    The rate counts every output token that fell inside the window, of every
    request, whenever it was sent or answered (the run waits for the requests
    in flight when the window closes). ``ttft`` is the time to first token as
    the client is owed it: the response's ``ttft_s``, plus the front's share
    (round trip minus ``latency_s``, all charged to the first token), plus how
    late the request was sent after it was due. ``tpot`` is the mean gap
    between a request's output tokens. A request that failed, was refused,
    truncated or is unanswered misses every latency."""
    window = [r for r in records if in_window(r, t0, t_end, by)]
    ok = [r for r in window if r["status"] == "ok"]
    ttft, tpot, front, late = [], [], [], []
    for r in ok:
        rtt = r["done"] - r["sent"]
        over = max(rtt - r["latency_s"], 0.0)
        ttft.append((r["ttft_s"] + over + (r["sent"] - r["due"])) * 1e3)
        front.append(over * 1e3)
        n = len(r["output_ids"])
        if n > 1:
            tpot.append((r["latency_s"] - r["ttft_s"]) / (n - 1) * 1e3)
    for r in window:
        late.append((r["sent"] - r["due"]) * 1e3)
    tokens = sum(tokens_inside(r, t0, t_end) for r in records
                 if r["status"] == "ok")
    statuses = {}
    for r in window:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    return {"attempted": len(window) + unanswered,
            "failed": len(window) - len(ok) + unanswered,
            "statuses": statuses, "ok": ok,
            "tokens_completed": tokens,
            "window_s": t_end - t0,
            "samples": {"ttft_ms": ttft, "tpot_ms": tpot,
                        "front_overhead_ms": front, "lateness_ms": late}}


def end_to_end(red: dict) -> dict:
    """Metric name -> value; a metric with no sample is left out."""
    out = {}
    if red["tokens_completed"]:
        out["serve_tok_per_s"] = red["tokens_completed"] / red["window_s"]
    for name, key in (("ttft_p95_ms", "ttft_ms"), ("tpot_p95_ms", "tpot_ms")):
        if red["samples"][key]:
            out[name] = stats.percentile(red["samples"][key], 95)
    return out
