"""The trace reduction on a small trace recorded on one v5e chip
(``data/record_trace.py``: five rounds of ``probe_matmul`` and ``probe_scan``
with a 20 ms host sleep between them, 0.112 s by the host's clock)."""
import os

import pytest

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "probe.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE)


def test_names(reduced):
    assert trace_reduce.program_of("jit_mixed_fn(123456)") == "mixed_fn"
    assert trace_reduce.program_of("jit_step(9)") == "step"
    assert trace_reduce.op_of(
        "%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8,128] %p)") == "fusion.3"


def test_programs_and_their_device_time(reduced):
    assert {k: len(v) for k, v in reduced["programs"].items()} == {
        "probe_matmul": 5, "probe_scan": 5}
    # one matmul+tanh of 1024^3 takes ~16 us on the chip; the scan holds four
    assert 10e-6 < min(reduced["programs"]["probe_matmul"]) < 25e-6
    ratio = (sum(reduced["programs"]["probe_scan"])
             / sum(reduced["programs"]["probe_matmul"]))
    assert 3.0 < ratio < 4.5


def test_busy_idle_and_window(reduced):
    assert len(reduced["devices"]) == 1
    assert reduced["window_s"] == pytest.approx(0.111, abs=0.005)
    assert 0.0003 < reduced["busy_s"] < 0.0004
    assert reduced["busy_s"] <= sum(map(sum, reduced["programs"].values()))


def test_top_ops_are_self_times_under_their_program(reduced):
    ops = dict(reduced["top_ops"])
    assert reduced["top_ops"][0][0] == "probe_scan/convolution_tanh_fusion.2"
    # the while loop's own time is what its body does not cover
    assert ops["probe_scan/while"] < 1e-5
    assert sum(ops.values()) <= reduced["busy_s"] * 1.05


def test_idle_gaps_are_blamed_on_the_host_annotation_that_covers_them(
        reduced):
    gaps = dict(reduced["idle_gaps"])
    assert reduced["idle_gaps"][0][0] == "bench.sleep"
    assert gaps["bench.sleep"] == pytest.approx(0.1, abs=0.02)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # device and host clocks differ by milliseconds; run ids align them
    assert 0.001 < abs(reduced["clock_offset_s"]) < 0.05


def test_union_and_self_times_on_hand_made_intervals():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    got = dict(trace_reduce.self_times(
        [(0.0, 10.0, "while"), (1.0, 4.0, "a"), (5.0, 9.0, "b"),
         (12.0, 13.0, "c")]))
    assert got == {"while": 3.0, "a": 3.0, "b": 4.0, "c": 1.0}
    assert reduced_collective_free()


def reduced_collective_free():
    import jax
    pd = jax.profiler.ProfileData.from_file(TRACE)
    return trace_reduce.collective_exposed_s(pd) == 0.0
