"""Percentiles and sample counts, lateness accounting, histogram quantiles,
the worst-leaf comparison, and FLOP and byte functions against hand counts."""
import math

import pytest

from benchmark import roofline, serve_metrics, stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([7], 95, 7.0),
])
def test_percentile_interpolates_between_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error_and_tail_support_counts():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    assert stats.tail_support(200, 95) == 10
    assert stats.tail_support(65, 95) == 3


def test_spread_is_the_interquartile_share_of_the_median():
    xs = [100, 101, 102, 103, 104, 105]
    assert stats.spread(xs) == pytest.approx((104.25 - 100.75) / 102.5)


def test_histogram_quantile_reads_only_what_the_window_gained():
    before = [(0.001, 10), (0.01, 10), (math.inf, 10)]
    after = [(0.001, 10), (0.01, 30), (math.inf, 30)]
    assert stats.histogram_quantile(before, after, 0.5) == pytest.approx(
        0.001 + 0.009 * 0.5)
    assert stats.histogram_quantile(before, before, 0.5) is None


def test_worst_leaf_gap_is_a_gap_of_norms_over_leaf_or_median_norm():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 2e-9}
    out = stats.worst_leaf_gap(got, ref)
    assert out["leaf"] == "a" and out["gap"] == pytest.approx(0.1)
    assert stats.worst_leaf_gap({"a": 0.0, "b": 0.0, "c": 0.0}, ref)[
        "gap"] == pytest.approx(1.0)
    with pytest.raises(ValueError):
        stats.worst_leaf_gap({"a": 1.0}, ref)


def rec(i, due, sent, done, ttft, lat, n_out, status="ok"):
    return {"index": i, "group": i, "due": due, "sent": sent, "done": done,
            "ttft_s": ttft, "latency_s": lat, "status": status,
            "output_ids": list(range(n_out)), "n_prompt": 4, "asked": n_out}


def test_open_loop_lateness_and_front_share_are_charged_to_the_first_token():
    records = [
        rec(0, 10.0, 10.5, 12.6, 1.0, 2.0, 11),        # sent 0.5 s late
        rec(1, 10.2, 10.2, 11.3, 0.5, 1.0, 6),
        rec(2, 9.0, 9.0, 10.4, 0.2, 1.3, 5),           # due before the window
        rec(3, 10.4, 10.4, 10.6, None, 0.1, 0, "http_429"),
    ]
    red = serve_metrics.reduce(records, 10.0, 20.0, by="due",
                               unanswered=1)
    assert red["attempted"] == 4 and red["failed"] == 2
    ttft = sorted(red["samples"]["ttft_ms"])
    assert ttft == pytest.approx([600.0, 1600.0])   # 0.5+0.1 ; 1.0+0.1+0.5
    assert sorted(red["samples"]["tpot_ms"]) == pytest.approx([100.0, 100.0])
    assert sorted(red["samples"]["lateness_ms"]) == pytest.approx(
        [0.0, 0.0, 500.0])
    # every token that fell inside the window: request 2's first token came
    # at 9.3 and its other 4 over (9.3, 10.4], 4 x 0.4 / 1.1 of them inside
    assert red["tokens_completed"] == pytest.approx(11 + 6 + 4 * 0.4 / 1.1)
    e2e = serve_metrics.end_to_end(red)
    assert e2e["serve_tok_per_s"] == pytest.approx(
        red["tokens_completed"] / 10.0)
    assert e2e["ttft_p95_ms"] == pytest.approx(1550.0)


def test_closed_loop_window_is_by_reply_time_and_empty_metrics_are_left_out():
    records = [rec(0, 5.0, 5.0, 6.0, 0.3, 0.9, 4, "truncated"),
               rec(1, 9.0, 9.0, 12.0, 1.0, 3.0, 5)]     # answered after it
    red = serve_metrics.reduce(records, 0.0, 10.0, by="done",
                               unanswered=0)
    assert red["attempted"] == 1 and red["failed"] == 1
    assert red["samples"]["ttft_ms"] == []
    # request 1 is outside the latency samples, but its first token (at
    # 10.0) and the tokens before the window closed are in the rate: none
    # here, the first token came as the window closed
    assert red["tokens_completed"] == pytest.approx(0.0)
    assert serve_metrics.end_to_end(red) == {}


def test_tokens_inside_places_the_first_token_and_spreads_the_rest():
    r = rec(0, 0.0, 0.0, 10.0, 2.0, 10.0, 9)     # first at 2.0, 8 over (2,10]
    assert serve_metrics.tokens_inside(r, 0.0, 20.0) == pytest.approx(9.0)
    assert serve_metrics.tokens_inside(r, 4.0, 6.0) == pytest.approx(2.0)
    assert serve_metrics.tokens_inside(r, 1.0, 3.0) == pytest.approx(2.0)
    assert serve_metrics.tokens_inside(r, 11.0, 12.0) == 0.0


GPT3_XL_14 = {"L": 14, "H": 2048, "heads": 16, "F": 8192, "V": 50304,
              "P": 2048, "eps": 1e-5}


def test_train_flops_against_a_hand_count():
    # per layer 12 H^2 = 50,331,648; x14 = 704,643,072; head 103,022,592
    assert roofline.matmul_params(GPT3_XL_14) == 807_665_664
    # causal attention, mean context 1024: 4 x 14 x 2048 x 1024
    assert roofline.attention_flops_per_token(GPT3_XL_14, 1024) == 117_440_512
    want = 3 * (2 * 807_665_664 + 117_440_512)
    assert roofline.train_flops_per_token(GPT3_XL_14, 2048) == want
    assert want == pytest.approx(5.2e9, rel=0.01)


def test_bytes_against_hand_counts():
    d = dict(GPT3_XL_14, L=24)
    # 24 x (4 H^2 + 2 H F + 9 H + F) + (V + P + 2) H parameters, 2 B each
    params = 24 * (4 * 2048**2 + 2 * 2048 * 8192 + 9 * 2048 + 8192) \
        + (50304 + 2048 + 2) * 2048
    assert params == 1_315_819_520      # the count PR 21 read on the chip
    assert roofline.weight_bytes(d, 2) == 2 * params
    assert roofline.kv_bytes_per_token(d, 2) == 2 * 24 * 2048 * 2
    assert roofline.decode_tick_bytes(d, 1000, 2, 2) \
        == 2 * params + 1000 * 196_608
    assert roofline.paged_attention_bytes([10, 20], 16, 128, 2) \
        == 2 * 30 * 2048 * 2
    assert roofline.flash_attention_flops(2, 2048, 16, 128) \
        == 4 * 2 * 16 * 2048 * 2048 * 128 / 2


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    assert roofline.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
    r = roofline.roofline_seconds(197e12, 819e9 * 2, roofline.peaks_for(
        "TPU v5 lite"))
    assert r == {"seconds": 2.0, "bound": "memory"}
