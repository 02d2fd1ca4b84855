"""``kv_read_amplification`` on hand-made span tables: pages the attention
path reads over the distinct live pages behind them, and None where nothing
stamps them (no issue phase, another system's spans, the parent's program)."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def reader():
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "kv_read_amplification.py")
    spec = importlib.util.spec_from_file_location("reader_kv_read", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def issue(t, kind, read=None, live=None):
    """One ``llm.issue.*`` phase; ``read`` None: a program that does not
    stamp the page counts."""
    attrs = {"live_rows": 31, "issue_seq": int(t * 10)}
    if read is not None:
        attrs.update(kv_pages_read=read, kv_pages_live=live)
    return {"name": "llm.issue." + kind, "ts": t, "dur": 0.003,
            "parent_id": None, "span_id": f"{kind}@{t}", "attrs": attrs,
            "events": []}


DECODE_ONLY = [issue(0.0, "decode", 465, 465), issue(1.0, "decode", 470, 470)]


@pytest.mark.parametrize("table,want", [
    # decode ticks through the kernel: every live page read once
    (DECODE_ONLY, 1.0),
    # a mixed tick: 64 chunk rows of two prompts read their sequences' pages
    # again and again beside 31 decode rows
    (DECODE_ONLY + [issue(2.0, "mixed", 465 + 520, 465 + 25)],
     (935 + 985) / (935 + 490)),
    # the gathered path: every table entry of every row
    ([issue(0.0, "decode", 32 * 128, 465)], 4096 / 465),
    # one dispatch is enough
    ([issue(0.0, "slab", 30, 20)], 1.5),
    # dispatches that do not carry the attrs add nothing
    ([issue(0.0, "decode")] + DECODE_ONLY, 1.0)],
    ids=["decode-only", "with-a-mixed-tick", "gathered", "one-dispatch",
         "beside-unstamped"])
def test_pages_read_over_distinct_live_pages(table, want):
    assert reader().compute(table) == pytest.approx(want)


@pytest.mark.parametrize("table", [
    [], [issue(0.0, "decode")],
    [{"name": "fit.dispatch", "ts": 0.0, "dur": 0.001, "parent_id": None,
      "span_id": "f", "attrs": {}, "events": []}],
    [issue(0.0, "mixed", 0, 0)]],
    ids=["empty", "parents-program", "another-system", "nothing-live"])
def test_nothing_to_read_returns_none_and_does_not_raise(table):
    assert reader().compute(table) is None


def test_read_goes_through_the_programs_own_table():
    from paddle_tpu.observability import tracing
    tracing.disable()
    tracing.clear()
    assert reader().read({}, None) is None             # an untraced run
    tracing.enable()
    try:
        with tracing.phase("llm.issue.decode") as ph:
            ph.set_attr("kv_pages_read", 6).set_attr("kv_pages_live", 4)
        got = reader().read({}, None)
    finally:
        tracing.disable()
        tracing.clear()
    assert got == pytest.approx(1.5)
