"""The readers of the marks inside ``llm.issue.*`` (PR 37) on hand-made span
tables: a mixed and a decode dispatch with known marks give each metric its
known value; an ``llm.loop.idle`` between a wait and an issue leaves that pair
out of the host's turns; a table without marks (the parent commit), an empty
one and one dispatch give None, None and that dispatch's own value."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1e-3
# emit, admit, then inside the issue phase: start to packed, to staged, to
# launched, to booked, to the phase's end; then blocked on the device
MIXED = dict(emit=2.0, admit=1.0, pack=1.5, stage=0.75, launch=0.5,
             book=0.25, stamp=0.125, wait=10.0)
DECODE = dict(emit=1.0, admit=0.5, pack=0.25, stage=0.5, launch=0.375,
              book=0.125, stamp=0.0625, wait=6.0)


def turn(d):
    return d["emit"] + d["admit"] + d["pack"] + d["stage"] + d["launch"]


WANT = {
    "host_turn_ms.mixed": turn(MIXED), "host_turn_ms.decode": turn(DECODE),
    "issue_pack_ms.mixed": MIXED["pack"],
    "issue_pack_ms.decode": DECODE["pack"],
    "issue_stage_ms.mixed": MIXED["stage"],
    "issue_stage_ms.decode": DECODE["stage"],
    "issue_launch_ms.mixed": MIXED["launch"],
    "issue_launch_ms.decode": DECODE["launch"],
    "trace_stamp_ms_per_dispatch": (MIXED["stamp"] + DECODE["stamp"]) / 2,
    # 10 ms idle over two executions, less the mean turn
    "device_turnaround_ms_per_dispatch":
        10.0 / 2 - (turn(MIXED) + turn(DECODE)) / 2}
NAMES = tuple(WANT)
TRACE = {"devices": [{"name": "/device:TPU:0"}], "window_s": 0.030,
         "busy_s": 0.020, "programs": {"mixed_fn": [0.012],
                                       "decode_fn": [0.008],
                                       "step": [0.5, 0.5]}}


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def compute(name, table, trace=TRACE):
    mod = reader(name)
    if name == "device_turnaround_ms_per_dispatch":
        return mod.compute(table, trace)
    return mod.compute(table)


def span(name, ts, dur, attrs=None, events=()):
    return {"name": name, "ts": ts, "dur": dur, "parent_id": None,
            "span_id": f"{name}@{ts}", "attrs": attrs or {},
            "events": [{"ts": t, "name": n} for t, n in events]}


def iteration(t, kind, d, seq, marks=True):
    """One loop iteration from ``t`` (seconds; the previous wait ended
    there): emit, admit, the issue phase with its marks, the wait. Returns
    the spans and where the wait ends."""
    t_admit = t + d["emit"] * MS
    t_issue = t_admit + d["admit"] * MS
    at, events = t_issue, []
    for mark, part in (("packed", "pack"), ("staged", "stage"),
                       ("launched", "launch"), ("booked", "book")):
        at += d[part] * MS
        events.append((at, mark))
    t_wait = at + d["stamp"] * MS
    spans = [span("llm.drain.emit", t, d["emit"] * MS),
             span("llm.loop.admit", t_admit, d["admit"] * MS),
             span("llm.issue." + kind, t_issue, t_wait - t_issue,
                  attrs={"issue_seq": seq, "live_rows": 3},
                  events=events if marks else ()),
             span("llm.drain.wait", t_wait, d["wait"] * MS)]
    return spans, t_wait + d["wait"] * MS


def table(marks=True):
    """A wait that ends at 1.0, then a mixed and a decode dispatch."""
    first = [span("llm.drain.wait", 0.99, 0.01)]
    a, t = iteration(1.0, "mixed", MIXED, 1, marks)
    b, _ = iteration(t, "decode", DECODE, 2, marks)
    return first + a + b


@pytest.mark.parametrize("name", NAMES)
def test_known_marks_give_the_known_value(name):
    assert compute(name, table()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_pack_stage_and_launch_are_no_more_than_the_turn(kind):
    parts = sum(compute(f"issue_{p}_ms.{kind}", table())
                for p in ("pack", "stage", "launch"))
    assert parts < compute(f"host_turn_ms.{kind}", table())


@pytest.mark.parametrize("name", NAMES)
def test_no_marks_and_no_spans_give_none(name):
    assert compute(name, []) is None
    assert compute(name, table(marks=False)) is None    # the parent commit
    fit = [span("fit.dispatch", 0.0, 0.002)]
    assert compute(name, fit) is None


@pytest.mark.parametrize("name", NAMES)
def test_one_dispatch_gives_its_own_value(name):
    kind = name.rsplit(".", 1)[1] if "." in name else "mixed"
    d = {"mixed": MIXED, "decode": DECODE}[kind]
    one = [span("llm.drain.wait", 0.99, 0.01)] + iteration(1.0, kind, d, 1)[0]
    want = {"host_turn_ms": turn(d), "issue_pack_ms": d["pack"],
            "issue_stage_ms": d["stage"], "issue_launch_ms": d["launch"],
            "trace_stamp_ms_per_dispatch": d["stamp"],
            "device_turnaround_ms_per_dispatch": 10.0 - turn(d)}[
                name.split(".")[0]]
    trace = dict(TRACE, programs={"mixed_fn": [0.012]})
    assert compute(name, one, trace) == pytest.approx(want)
    # the other kind's table says nothing of this kind
    if "." in name:
        other = {"mixed": "decode", "decode": "mixed"}[kind]
        assert compute(name, iteration(1.0, other, d, 1)[0]) is None


@pytest.mark.parametrize("name", ["host_turn_ms.mixed", "host_turn_ms.decode",
                                  "device_turnaround_ms_per_dispatch"])
def test_an_idle_wait_between_is_not_a_turn(name):
    """The engine had nothing to do between the wait and the issue: the
    pair is left out; so is a dispatch with no wait before it at all."""
    kind = "decode" if name.endswith("decode") else "mixed"
    d = {"mixed": MIXED, "decode": DECODE}[kind]
    it, t = iteration(1.0, kind, d, 1)
    idle = [span("llm.drain.wait", 0.90, 0.01),
            span("llm.loop.idle", 0.92, 0.05)]
    assert compute(name, idle + it) is None
    assert compute(name, it) is None
    # a second dispatch right after the first's wait is a turn again
    again, _ = iteration(t, kind, d, 2)
    got = compute(name, idle + it + again,
                  dict(TRACE, programs={"mixed_fn": [0.01, 0.01]}))
    want = turn(d) if name != "device_turnaround_ms_per_dispatch" \
        else 10.0 / 2 - turn(d)
    assert got == pytest.approx(want)


def test_a_phase_that_launched_nothing_does_not_end_the_turn():
    """``_issue`` may find every live slot finished and return: its phase
    has no mark and no ``issue_seq``, and the device goes on waiting."""
    it, t = iteration(1.0, "decode", DECODE, 1)
    empty = span("llm.issue.decode", t, 0.001)
    again, _ = iteration(t + 0.001, "decode", DECODE, 2)
    one = [span("llm.drain.wait", 0.99, 0.01)] + it
    turns = reader("_marks").host_turns_ms(one + [empty] + again, "decode")
    assert turns == pytest.approx([turn(DECODE), turn(DECODE) + 1.0])
    # a launch of a kind without marks (a slab) ends it: the device works
    slab = span("llm.issue.slab", t, 0.001, attrs={"issue_seq": 9})
    turns = reader("_marks").host_turns_ms(one + [slab] + again, "decode")
    assert turns == pytest.approx([turn(DECODE)])


@pytest.mark.parametrize("trace", [
    None, dict(TRACE, devices=[]), dict(TRACE, window_s=0.0),
    dict(TRACE, programs={"step": [0.5]})],
    ids=["untraced", "no-device-plane", "empty-window", "no-engine-program"])
def test_turnaround_without_a_device_trace_is_none(trace):
    assert compute("device_turnaround_ms_per_dispatch", table(),
                   trace) is None


@pytest.mark.parametrize("name", NAMES + ("spans_dropped",))
def test_read_goes_through_the_programs_own_table(name):
    from paddle_tpu.observability import tracing
    tracing.disable()
    tracing.clear()
    if name != "spans_dropped":
        assert reader(name).read({}, TRACE) is None     # an untraced run
    tracing.enable()
    try:
        for kind in ("mixed", "decode"):
            with tracing.phase("llm.drain.wait"):
                pass
            with tracing.phase("llm.issue." + kind) as ph:
                for mark in ("packed", "staged", "launched", "booked"):
                    ph.add_event(mark)
                ph.set_attr("issue_seq", 1)
        got = reader(name).read({}, TRACE)
    finally:
        tracing.disable()
        tracing.clear()
    if name == "spans_dropped":
        assert got == 0
    elif name == "device_turnaround_ms_per_dispatch":
        assert 0 < got <= 5.0       # 10 ms over two, less a turn of us
    else:
        assert got is not None and got >= 0


def test_spans_dropped_counts_the_ring_and_is_none_on_the_parent(monkeypatch):
    from paddle_tpu.observability import tracing
    tracing.disable()
    tracing.clear()
    tracing.enable(capacity=4)
    try:
        for i in range(6):
            with tracing.phase(f"p{i}"):
                pass
        assert reader("spans_dropped").read({}, None) == 2
        monkeypatch.delattr(tracing, "dropped_spans")   # a program without
        assert reader("spans_dropped").read({}, None) is None
    finally:
        tracing.disable()
        tracing.set_capacity(tracing.DEFAULT_TABLE_CAP)
        tracing.clear()
