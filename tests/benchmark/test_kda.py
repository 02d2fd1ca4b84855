"""The delta-rule / latent-attention configuration's share of the benchmark:
the configuration file against the catalog row, the cell's traffic letter for
letter and its fit in the latent group and the state rows, the parameter
count and roofline arithmetic against hand counts, the new reader on canned
span tables, the plain reference against itself (blocks of queries, the
shares of a slice) and its controls, and the rehearsal cell end to end on the
CPU (through ``run.py``, a process of its own)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import roofline_kda as rk
from benchmark import weights_kda
from benchmark.reference import kimi_linear as ref
from benchmark.traffic import shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "reason_closed_kda"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")


def load(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


CONFIG = load("configs", "kimi-linear-48b-a3b-serve-ep16.json")
D = weights_kda.dims_of(CONFIG)
TD = weights_kda.dims_of(load("configs", "rehearsal-tiny-kda.json"))
MIX = load("workloads", CELL + ".json")
# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, source_url SOURCE), copied here: the guide is not part of a checkout
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
CUT = {"num_experts": 16, "vocab_size": 20480}


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, attrs):
    return {"name": name, "ts": 0.0, "dur": 0.001, "parent_id": None,
            "attrs": attrs, "events": []}


def latent(read, live, held=0):
    return {"latent": {"read": read, "live": live, "bytes_held": held,
                       "page_bytes": 143_360}}


# -- the configuration file and BENCHMARK.json ---------------------------------

def test_configuration_equals_the_catalog_row_but_for_the_stated_cuts():
    for key, value in CATALOG.items():
        assert CONFIG[key] == CUT.get(key, value), key
    assert CONFIG["published"] == {"num_experts": 256, "vocab_size": 163840}
    assert "num_layers" not in CONFIG and CONFIG["experts_held"] == [0, 16]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert CONFIG["reduced"] == entry["reduced"] == ["num_experts",
                                                     "vocab_size"]
    # no width is cut, and none could be named
    assert not any(w in k for k in CONFIG["reduced"]
                   for w in ("hidden", "_rank", "_dim"))
    assert CONFIG["system"] == "serve_kda"
    assert "v5e-16" in CONFIG["deployment"] \
        and "sixteen chips share every layer" in CONFIG["deployment"]
    assert sum(a.startswith(("pre-norm", "KDA: q, k, v", "KDA decay",
                             "KDA write strength", "MLA:", "router:",
                             "weights:", "the stored latent row"))
               for a in CONFIG["assumed"]) == 8
    # the guide's floors: every period whole at full depth, 8+ experts, 1/8
    # of the vocabulary
    assert D["L"] == 27 and D["kinds"].count("kda") == 20 \
        and D["kinds"].count("mla") == 7
    assert D["kinds"][:4] == ("kda", "kda", "kda", "mla") \
        and D["kinds"][-1] == "mla" and D["dense"] == (0,)
    assert D["count"] >= 8 and D["E"] == 256 and D["V"] * 8 == 163840
    assert (D["latent"], D["latent_width"]) == (576, 640)


def test_the_benchmark_gains_one_configuration_one_cell_and_one_reader():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], CELL, 1)
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG["name"]] == [CELL]
    assert BENCH["workloads"][-1] is cell
    assert BENCH["configs"][-1]["name"] == CONFIG["name"]
    by = {m["name"]: m for m in BENCH["per_layer"]}
    m = BENCH["per_layer"][-1]
    assert m == {"name": "kda_decode_roofline_share", "unit": "%",
                 "better": "higher", "source": "device_trace",
                 "layer": "engine programs", "moves": "tpot_p95_ms",
                 "workloads": [CELL]}
    assert hasattr(reader(m["name"]), "read")
    # what chat_closed_hybrid reports of serving, of experts and of state,
    # it reports
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "chat_closed" in m.get("workloads", []) \
                and m["name"] != "prefix_hit_share":
            assert CELL in m["workloads"], m["name"]
    for name in ("moe_expert_load_max_over_mean", "moe_held_pair_share",
                 "state_bytes_per_token"):
        assert by[name]["workloads"][-1] == CELL
    # the prefix cache is off beside recurrent state: nothing to read there
    assert CELL not in by["prefix_hit_share"]["workloads"]
    # (``cache_bytes_per_token`` and ``swa_kv_bytes_read_per_token`` stay
    # the window cell's alone: tests/benchmark/test_swa.py pins their lists,
    # and widening them is a benchmark issue's; their readers are generic
    # over ``kv_groups`` and are held to the latent group below)


def test_traffic_is_the_issues_letter_for_letter_and_fits_pages_and_state():
    assert MIX["kind"] == "closed_loop" and MIX["clients"] == 48
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.9, "min": 256, "max": 6144}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.4, "min": 384, "max": 1536}
    assert (MIX["cycle"], MIX["pairing_seed"], MIX["ramp_s"]) == (16, 0,
                                                                  16.0)
    assert MIX["prime"] == {"prompt_len": 64, "max_new_tokens": 16}
    # the replies before the window are cut from the issue's 48 so that a
    # tree's first traced run ends inside the driver's 360 s (PERF.md section
    # 4); not the traffic, and not the traced 3 s
    assert MIX["warmup"]["min_requests"] == 16 and MIX["trace_s"] == 3.0
    eng = CONFIG["engine"]
    assert eng == {"max_seqs": 48, "page_size": 16, "max_len": 7680,
                   "kv_dtype": "bf16", "prefill_chunk": 256,
                   "num_pages": 23041}
    check = load("checks", CELL + ".json")
    assert check["control"] == "fp8" and check["sample"] == 4
    assert set(check) == {"sample", "pad_to", "control", "worst_gap_limit",
                          "argmax_share_min"}
    assert check["pad_to"] % ref.QUERY_BLOCK == 0
    prompts = shapes.cycle(MIX["prompt_len"], 16)
    outputs = shapes.cycle(MIX["output_len"], 16)
    assert min(prompts) == 256 and 5400 < max(prompts) < 5600
    assert min(outputs) == 384 and max(outputs) == 1536
    # the pairing is fixed: the longest request is the longest prompt's
    pair = shapes.rng(0, 7).permutation(16)
    longest = max(prompts[i] + outputs[int(pair[i])] for i in range(16))
    assert longest == 6319 <= check["pad_to"] <= eng["max_len"]
    assert max(prompts) + max(outputs) <= eng["max_len"]
    # every slot's longest sequence has its pages, every caller its slot and
    # its state row: nothing is truncated, nothing queues for a slot
    assert MIX["clients"] == eng["max_seqs"]
    assert eng["max_seqs"] * (eng["max_len"] // eng["page_size"]) \
        == eng["num_pages"] - 1
    assert 1400 < sum(prompts) / 16 < 1500 and 800 < sum(outputs) / 16 < 850


# -- sizes: parameters, pages, state, the roofline's arithmetic ----------------

def test_parameter_count_state_and_page_bytes_are_the_issues():
    assert rk.kda_params(D) == 39_518_368
    assert rk.mla_params(D) == 29_114_880
    assert rk.dense_params(D) == 63_700_992
    assert rk.expert_params(D) == 7_077_888
    assert rk.shared_params(D) + rk.router_params(D) == 7_667_968
    norms = 27 * 2 * 2304 + 2304
    assert weights_kda.n_params(D) == rk.total_params(D) == (
        20 * 39_518_368 + 7 * 29_114_880 + 63_700_992
        + 26 * (16 * 7_077_888 + 7_667_968) + 2 * 20_480 * 2304 + norms) \
        == 4_296_139_648
    assert rk.weight_bytes(D) == pytest.approx(8.59e9, rel=1e-3)
    # a slot's state: 20 layers of a [32, 128, 128] float32 state and a
    # [3, 12288] bf16 tail
    assert rk.state_row_bytes(D) == 20 * (2_097_152 + 73_728) == 43_417_600
    assert 49 * rk.state_row_bytes(D) == pytest.approx(2.13e9, rel=2e-3)
    # a token's latent rows: 7 layers of 640 stored bf16 values
    assert rk.page_bytes(D, 16) == 16 * 7 * 1280 == 143_360
    assert 23_041 * 143_360 == pytest.approx(3.30e9, rel=2e-3)
    total = rk.weight_bytes(D) + 49 * rk.state_row_bytes(D) \
        + 23_041 * 143_360
    assert 0.82 < total / 16_909_336_064 < 0.84


def test_roofline_counts_match_the_hand_counts():
    fixed = (20 * 39_518_368 + 7 * 29_114_880 + 63_700_992
             + 26 * 7_667_968 + 20_480 * 2304 + 55 * 2304)
    assert rk.fixed_params(D) == fixed
    assert rk.decode_tick_bytes(D, 0, 0, 0, 16) == 2 * fixed
    # the issue's tick: 48 rows x 8 experts over 256 touch ~12.5 of 16 held
    # experts a layer; 48 rows of ~2k tokens
    tick = rk.decode_tick_bytes(D, 26 * 12.5, 48, 48 * 128, 16)
    assert tick == 2 * fixed + 26 * 12.5 * 2 * 7_077_888 \
        + 2 * 48 * 43_417_600 + 48 * 128 * 143_360
    assert 14.0 < tick / 819e9 * 1e3 < 16.0
    assert 2 * 48 * 43_417_600 == pytest.approx(4.2e9, rel=1e-2)
    flops = rk.token_flops(D, 2048)
    assert flops == 2.0 * (fixed + 26 * 8 * 16 / 256 * 7_077_888
                           + 20 * 4 * 32 * 128 * 128
                           + 7 * 32 * (576 + 512) * 2048)
    assert rk.token_flops(D, 100) < rk.token_flops(D, 512)


# -- the reader ----------------------------------------------------------------

def test_roofline_share_reads_the_median_traced_decode_tick():
    mod = reader("kda_decode_roofline_share")
    spans = []
    for seq, (pages, rows, touched) in enumerate(
            [(5000, 47, 300), (6144, 48, 325), (7000, 48, 340)]):
        spans.append(span("llm.issue.decode", {
            "issue_seq": seq, "state_rows": rows,
            "kv_groups": latent(pages, pages)}))
        spans.append(span("llm.drain.emit", {"issue_seq": seq,
                                             "experts_touched": touched}))
    spans.append(span("llm.issue.mixed", {
        "issue_seq": 9, "state_rows": 50,
        "kv_groups": latent(10 ** 6, 9000)}))
    want_ms = rk.decode_tick_bytes(D, 325, 48, 6144, 16) / 819e9 * 1e3
    assert mod.compute(spans, D, 16, 2 * want_ms, 819e9) == pytest.approx(50)
    # a program with no latent group (the parent, another model): nothing
    assert mod.compute([span("llm.issue.decode", {
        "kv_pages_live": 9, "issue_seq": 0, "state_rows": 3})],
        D, 16, 30.0, 819e9) is None
    other = {"full": {"read": 1, "live": 1}, "window": {"read": 1, "live": 1}}
    assert mod.compute(
        [span("llm.issue.decode", {"issue_seq": 0, "kv_groups": other}),
         span("llm.drain.emit", {"issue_seq": 0, "experts_touched": 3})],
        D, 16, 30.0, 819e9) is None
    assert mod.compute(spans, D, 16, None, 819e9) is None
    assert mod.read({"dims": {"L": 24}, "peaks": {}}, None) is None
    assert mod.read({"dims": D, "peaks": None}, None) is None


def test_the_generic_readers_price_the_latent_group_at_its_own_bytes():
    """``cache_bytes_per_token`` and ``swa_kv_bytes_read_per_token`` read
    ``kv_groups`` whatever the groups are called."""
    spans = [span("llm.issue.decode", {
                 "kv_groups": latent(100, 100, held=100 * 143_360),
                 "context_tokens": 1600}),
             span("llm.issue.mixed", {
                 "kv_groups": latent(50, 40, held=40 * 143_360),
                 "context_tokens": 640}),
             span("llm.drain.emit", {"tokens": 48}),
             span("llm.drain.emit", {"tokens": 2})]
    assert reader("cache_bytes_per_token").compute(spans) == 8960
    assert reader("swa_kv_bytes_read_per_token").compute(spans) \
        == 150 * 143_360 / 50


# -- the weights and the reference ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return weights_kda.make(TD, 2 ** 31 + 9, jnp.float32)


def test_weights_are_seeded_under_the_programs_names(tiny_params):
    again = weights_kda.make(TD, 2 ** 31 + 9, jnp.float32)
    other = weights_kda.make(TD, 2 ** 31 + 10, jnp.float32)
    name = "layers.2.moe.w_in"
    assert tiny_params[name].shape == (4, 64, 64)        # the held share
    assert tiny_params["layers.2.moe.router"].shape == (64, 8)
    assert tiny_params["layers.2.moe.e_bias"].shape == (8,)
    assert np.array_equal(tiny_params[name], again[name])
    assert not np.array_equal(tiny_params[name], other[name])
    assert float(jnp.std(tiny_params[name])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(tiny_params["layers.2.moe.e_bias"])) < 0.03
    assert float(tiny_params["layers.0.post_norm.weight"].min()) == 1.0
    assert "layers.0.mlp.w_in.weight" in tiny_params \
        and "layers.0.moe.router" not in tiny_params
    # layer 4 (index 3) attends; the others keep a state
    assert tiny_params["layers.3.mixer.kv_a.weight"].shape == (64, 40)
    assert tiny_params["layers.1.mixer.conv_weight"].shape == (4, 96)
    a = np.exp(np.asarray(tiny_params["layers.1.mixer.A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(tiny_params["layers.1.mixer.dt_bias"])))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    # float32 whatever the weights' type
    bf16 = weights_kda.make(TD, 1, jnp.bfloat16)
    for leaf in ("moe.e_bias", "mixer.A_log", "mixer.dt_bias",
                 "mixer.g_bias"):
        assert bf16["layers.1." + leaf].dtype == jnp.float32
    assert bf16["layers.1.mixer.qkv_proj.weight"].dtype == jnp.bfloat16
    assert sum(int(np.prod(v.shape)) for v in tiny_params.values()) \
        == weights_kda.n_params(TD) == rk.total_params(TD)


def test_served_gaps_are_zero_for_the_references_own_tokens_and_the_control_is_not(
        tiny_params):
    ids = np.asarray(shapes.rng(3, 1).integers(0, TD["V"], (2, 64)),
                     np.int32)
    lg = ref.logits(tiny_params, ids, TD)
    own = np.zeros_like(ids)
    own[:, :-1] = np.argmax(np.asarray(lg), -1)[:, :-1]
    first, count = np.asarray([5, 9]), np.asarray([40, 50])
    got = ref.served_gaps(tiny_params, ids, first, count, own, TD, "fp8")
    assert int(got["mask"].sum()) == 90
    assert float(np.asarray(got["gap"]).max()) == 0.0
    assert float(np.asarray(got["control_gap"]).max()) > 1e-5


def test_reference_in_blocks_of_queries_is_the_reference_whole(
        tiny_params, monkeypatch):
    """64 positions in blocks of 8 queries (the path 6k tokens take); later
    tokens move nothing before them: the delta rule and the attention are
    causal."""
    ids = np.asarray(shapes.rng(4, 1).integers(0, TD["V"], (1, 64)),
                     np.int32)
    whole = ref.logits(tiny_params, ids, TD)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    blocks = ref.logits(tiny_params, ids, TD)
    np.testing.assert_allclose(blocks, whole, atol=2e-6, rtol=2e-6)
    moved = ids.copy()
    moved[0, 50:] = (moved[0, 50:] + 1) % TD["V"]
    again = ref.logits(tiny_params, moved, TD)
    np.testing.assert_allclose(again[0, :50], blocks[0, :50], atol=1e-6)
    assert float(jnp.abs(again[0, 50:] - blocks[0, 50:]).max()) > 1e-5


def test_the_references_delta_rule_is_the_rule_by_hand():
    """Two tokens, one head of two channels, by hand."""
    k = jnp.asarray([[[1.0, 0.0]], [[0.6, 0.8]]])
    v = jnp.asarray([[[2.0, 3.0]], [[1.0, -1.0]]])
    log_a = jnp.log(jnp.asarray([[[0.5, 0.5]], [[0.5, 0.25]]]))
    b = jnp.asarray([[1.0], [0.5]])
    o = ref.delta_rule(k, k, v, log_a, b)
    s1 = np.outer([1.0, 0.0], [2.0, 3.0])
    np.testing.assert_allclose(o[0, 0], s1.T @ [1.0, 0.0], atol=1e-6)
    d = np.diag([0.5, 0.25]) @ s1
    kk = np.asarray([0.6, 0.8])
    s2 = d - 0.5 * np.outer(kk, kk @ d) + 0.5 * np.outer(kk, [1.0, -1.0])
    np.testing.assert_allclose(o[1, 0], s2.T @ kk, atol=1e-6)


def test_the_references_shares_of_a_slice_sum_to_the_uncut_routed_layer(
        tiny_params):
    """The reference's own routed sum over experts 2-5 (what this
    configuration holds) plus the other four's is the sum over all eight:
    all scored, ``top_k`` taken, the held ones add."""
    lp = {k[len("layers.2."):]: v for k, v in tiny_params.items()
          if k.startswith("layers.2.")}
    whole = weights_kda.make(dict(TD, first=0, count=8), 2 ** 31 + 9,
                             jnp.float32)
    wp = {k[len("layers.2."):]: v for k, v in whole.items()
          if k.startswith("layers.2.")}
    x = jnp.asarray(shapes.rng(5, 1).normal(size=(16, 64)), jnp.float32)
    parts = sum(ref.routed(x, dict(wp, **{
        "moe.w_in": wp["moe.w_in"][f:f + c],
        "moe.w_out": wp["moe.w_out"][f:f + c]}), TD, held=(f, c))
        for f, c in ((0, 2), (2, 4), (6, 2)))
    np.testing.assert_allclose(parts, ref.routed(x, wp, TD, held=(0, 8)),
                               atol=2e-6, rtol=2e-6)
    assert lp["moe.w_in"].shape[0] == 4
    idx, gates = ref.route(x, wp, TD)
    np.testing.assert_allclose(gates.sum(-1), TD["routed_scale"], rtol=1e-6)


# -- the rehearsal cell, end to end --------------------------------------------

# the chip's readings of the cell (PERF.md sections 4 and 6, my chip runs,
# PR 39): (worst gap, share of served tokens that are the reference's best)
SOUND = [(1.0019, 0.8897), (0.9840, 0.8904), (0.5416, 0.8909),
         (0.6547, 0.8827), (0.6236, 0.8971), (0.8235, 0.8824),
         (0.6100, 0.8766),
         # after the driver's check read ``correct`` false once, on seed
         # 2120525478: that seed again on the same tree, and two more
         (0.9565, 0.8802), (0.5717, 0.9032), (0.6277, 0.9004)]
FP8 = [(1.3093, 0.4692), (1.9135, 0.4410), (1.5247, 0.4319),
       (1.2612, 0.4480)]
# the reference with bf16 operands (the program's own precision: the floor)
# and, inside it, a delta-rule state rounded to bf16 after every token
FLOOR = [(0.7230, 0.9172), (0.5608, 0.8962)]
STATE_FAULT = [(0.7230, 0.8432), (0.6410, 0.8357)]
# the PROGRAM with that fault put in (what its two delta-rule forms write to
# the state array rounded to bf16), the cell's own check
PROGRAM_FAULT = [(1.1830, 0.8537), (1.0645, 0.8350), (0.8374, 0.8380)]


def gaps_reading(worst, share, n=3000):
    """``n`` gaps of which ``share`` are 0 and the largest is ``worst``."""
    gaps = np.zeros(n, np.float32)
    miss = n - int(round(share * n))
    gaps[:miss] = np.linspace(worst, worst * 1e-3, miss)
    return gaps


@pytest.mark.parametrize("cell", [CELL, "rehearsal_kda"])
def test_the_check_holds_two_numbers_and_either_alone_refuses(cell):
    """The worst gap saturates at 27 layers (the fp8 control moves it 1.3 x,
    a bf16 delta-rule state not at all), so the check holds the share of
    served tokens that are the reference's best beside it: a run is correct
    by BOTH; the chip's fp8 readings and its readings of a bf16 state inside
    the program's own precision fail by the share, the sound runs and the
    floor (bf16 operands alone) pass."""
    from benchmark.systems import serve_kda
    spec = load("checks", cell + ".json")
    lim, share = spec["worst_gap_limit"], spec["argmax_share_min"]
    above = 1 - (1 - share) / 2
    got = serve_kda.held(gaps_reading(lim * 0.9, above), spec)
    assert got["correct"]
    assert got["argmax_share"] == pytest.approx(above, abs=1e-3)
    assert got["worst_gap"] == pytest.approx(lim * 0.9)
    assert not serve_kda.held(gaps_reading(lim * 1.1, above),
                              spec)["correct"]
    assert not serve_kda.held(gaps_reading(lim * 0.9, share - 0.05),
                              spec)["correct"]
    if cell != CELL:
        return
    for worst, sh in SOUND + FLOOR:
        assert serve_kda.held(gaps_reading(worst, sh), spec)["correct"]
    for worst, sh in FP8 + STATE_FAULT + PROGRAM_FAULT:
        assert not serve_kda.held(gaps_reading(worst, sh), spec)["correct"]
        # ... and by the share alone, were the worst gap inside its limit
        assert not serve_kda.held(gaps_reading(1.0, sh), spec)["correct"]
    # the worst gap alone passes the state fault: why the share is held
    assert max(w for w, _ in STATE_FAULT + PROGRAM_FAULT) < lim
    # room on the sound side of both limits (22 sound runs: mean share
    # 0.8893, sd 0.0047), the fp8 control far under
    assert max(w for w, _ in SOUND) < lim < min(w for w, _ in FP8)
    assert max(s for _, s in STATE_FAULT + PROGRAM_FAULT) < share < min(
        s for _, s in SOUND) - 0.015
    assert max(s for _, s in FP8) + 0.3 < share


def _names(code):
    """Every global or attribute name ``code`` and the code objects nested in
    it (closures, lambdas, comprehensions) read."""
    out = set(code.co_names)
    for c in code.co_consts:
        if hasattr(c, "co_names"):
            out |= _names(c)
    return out


def test_the_borrowed_run_reads_its_three_things_as_its_own_globals():
    """``serve_kda.run`` is ``serve_swa.run``'s code over other globals
    (``_with``). That holds only while ``run`` itself names the weights, the
    constructor and the check as globals of its module, and no helper it
    calls reads one of them (a helper keeps ``serve_swa``'s globals: it would
    make Laguna's weights or hold this cell to Laguna's reference)."""
    import types
    from benchmark.systems import serve_kda, serve_swa
    swapped = {"weights_swa", "build_net", "check_served"}
    assert swapped <= set(serve_swa.run.__code__.co_names)
    assert not serve_swa.run.__closure__
    helpers = {n for n in _names(serve_swa.run.__code__)
               if isinstance(vars(serve_swa).get(n), types.FunctionType)
               and n not in swapped}
    assert helpers                       # e.g. the counters' snapshot
    for n in sorted(helpers):
        assert not swapped & _names(vars(serve_swa)[n].__code__), n
    # what the borrowed run reads through those names is this module's
    run = serve_kda._with(serve_swa.run, weights_swa=weights_kda,
                          build_net=serve_kda.build_net,
                          check_served=serve_kda.check_served)
    assert run.__code__ is serve_swa.run.__code__
    assert run.__globals__["weights_swa"] is weights_kda
    assert run.__globals__["check_served"] is serve_kda.check_served
    assert run.__globals__["build_net"] is serve_kda.build_net
    assert serve_swa.run.__globals__["weights_swa"] is not weights_kda


def test_rehearsal_cell_walks_the_kda_driver_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearsal_kda", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    for name in ("state_bytes_per_token", "kv_read_amplification",
                 "tick_live_rows_p50", "moe_held_pair_share",
                 "moe_expert_load_max_over_mean"):
        assert name in line["metrics"], (name, line["metrics"])
    assert "prefix_hit_share" not in line["metrics"]
    # no peaks off the TPU: the share of a floor is not read
    assert "kda_decode_roofline_share" not in line["metrics"]
    assert 40 < line["metrics"]["moe_held_pair_share"]["value"] < 60
