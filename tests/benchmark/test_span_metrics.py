"""The five ``program_span`` readers on hand-made span tables: what they sum,
what they leave out, and what they return when the table holds one dispatch,
one request, or nothing (the parent commit, an untraced run)."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ("sched_host_ms_per_dispatch", "tick_live_rows_p50",
         "prefill_wait_mean_ms", "prefill_to_first_token_p50_ms",
         "fit_host_ms_per_step")


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, ts, dur, parent=None, attrs=None, events=()):
    return {"name": name, "ts": ts, "dur": dur, "parent_id": parent,
            "span_id": f"{name}@{ts}", "attrs": attrs or {},
            "events": [{"ts": t, "name": n, "attrs": a}
                       for t, n, a in events]}


def dispatch(t, rows, kind="mixed"):
    """One loop iteration from ``t``: 1 ms admit, 3 ms issue, 100 ms blocked
    on the device, 2 ms emit."""
    return [span("llm.loop.admit", t, 0.001),
            span("llm.issue." + kind, t + 0.001, 0.003,
                 attrs={"live_rows": rows, "issue_seq": int(t * 10)}),
            span("llm.drain.wait", t + 0.004, 0.100),
            span("llm.drain.emit", t + 0.104, 0.002)]


def request(root, t_admit, chunk_times, t_first_token):
    """Admitted at ``t_admit``, chunks at ``chunk_times``, first token
    delivered at ``t_first_token``."""
    last = chunk_times[-1]
    return [span("llm.prefill", t_admit, last - t_admit, parent=root,
                 events=[(t, "chunk", {"tokens": 64}) for t in chunk_times]),
            span("llm.first_token", last, t_first_token - last, parent=root)]


ENGINE = (dispatch(0.0, 30) + dispatch(1.0, 32, "decode")
          + dispatch(2.0, 31) + [span("llm.loop.idle", 3.0, 0.05),
                                 span("llm.loop.control", 3.05, 0.003)])
REQUESTS = (request("r1", 10.0, [10.2, 10.6], 11.0)
            + request("r2", 20.0, [20.5], 20.9)
            + request("r3", 30.0, [30.9, 31.3, 31.7], 32.5)
            # admitted, no chunk yet when the table was read
            + [span("llm.prefill", 40.0, 0.5, parent="r4")])
FIT = [s for t in (0.0, 0.25, 0.5) for s in (
    span("fit.next_batch", t, 0.0005), span("fit.dispatch", t + 0.001, 0.0015),
    span("fit.callbacks", t + 0.003, 0.2))] + [
    span("fit.next_batch", 0.75, 0.0005)]


def test_sched_host_ms_per_dispatch_leaves_out_the_waits():
    got = reader("sched_host_ms_per_dispatch").compute(ENGINE)
    # three times (1 + 3 + 2) ms, and the 3 ms of control ops, over three
    assert got == pytest.approx((3 * 6.0 + 3.0) / 3)


def test_tick_live_rows_p50():
    assert reader("tick_live_rows_p50").compute(ENGINE) == 31


def test_prefill_wait_and_to_first_token_tile_admission_to_first_token():
    wait = reader("prefill_wait_mean_ms").compute(REQUESTS)
    rest = reader("prefill_to_first_token_p50_ms").compute(REQUESTS)
    assert wait == pytest.approx(1600.0 / 3)    # 200, 500, 900: the mean
    assert rest == pytest.approx(800.0)         # 800, 400, 1600
    one = request("r1", 10.0, [10.2, 10.6], 11.0)
    assert reader("prefill_wait_mean_ms").compute(one) \
        + reader("prefill_to_first_token_p50_ms").compute(one) \
        == pytest.approx(1000.0)                # admission to first token


@pytest.mark.parametrize("waited", [8, 9])
def test_prefill_wait_mean_follows_the_share_that_waited(waited):
    """The wait is two-valued (same iteration, or whole mixed ticks): of 17
    prompts the median jumps from 0 to 391 ms between 8 and 9 that waited,
    the mean moves by one seventeenth of a tick."""
    table = [s for i in range(17) for s in request(
        f"r{i}", 10.0 * i, [10.0 * i + (0.391 if i < waited else 0.0)],
        10.0 * i + 1.0)]
    assert reader("prefill_wait_mean_ms").compute(table) \
        == pytest.approx(391.0 * waited / 17)


def test_fit_host_ms_per_step():
    mod = reader("fit_host_ms_per_step")
    assert mod.compute(FIT) == pytest.approx((4 * 0.5 + 3 * 1.5) / 3)
    assert mod.compute(FIT, 2) == pytest.approx((4 * 0.5 + 3 * 1.5) / 6)


@pytest.mark.parametrize("name,table,want", [
    ("sched_host_ms_per_dispatch", dispatch(0.0, 7), 6.0),
    ("tick_live_rows_p50", dispatch(0.0, 7), 7),
    ("prefill_wait_mean_ms", request("r", 1.0, [1.25], 2.0), 250.0),
    ("prefill_to_first_token_p50_ms", request("r", 1.0, [1.25], 2.0), 750.0),
    ("fit_host_ms_per_step", FIT[:3], 2.0)])
def test_one_dispatch_or_one_request_is_enough(name, table, want):
    assert reader(name).compute(table) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_returns_none_and_does_not_raise(name):
    mod = reader(name)
    assert mod.compute([]) is None
    # spans of the other system only
    other = FIT if name != "fit_host_ms_per_step" else ENGINE
    assert mod.compute(other) is None
    # a prompt without a chunk, a first token without its prefill
    assert mod.compute([span("llm.prefill", 0.0, 1.0, parent="x"),
                        span("llm.first_token", 1.0, 1.0, parent="y")]) \
        is None


@pytest.mark.parametrize("name", NAMES)
def test_read_goes_through_the_programs_own_table(name):
    from paddle_tpu.observability import tracing
    tracing.disable()
    tracing.clear()
    facts = {"steps_per_execution": 1}
    assert reader(name).read(facts, None) is None      # an untraced run
    tracing.enable()
    try:
        with tracing.phase("llm.loop.admit"):
            pass
        with tracing.phase("llm.issue.decode") as ph:
            ph.set_attr("live_rows", 3)
        root = tracing.start_span("llm.request")
        pre = tracing.start_span("llm.prefill", parent=root)
        pre.add_event("chunk", {"tokens": 8})
        pre.end()
        tracing.start_span("llm.first_token", parent=root).end()
        root.end()
        with tracing.phase("fit.next_batch"):
            pass
        with tracing.phase("fit.dispatch"):
            pass
        got = reader(name).read(facts, None)
    finally:
        tracing.disable()
        tracing.clear()
    assert got is not None and got >= 0
