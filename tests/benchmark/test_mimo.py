"""The sink / unequal-widths configuration's share of the benchmark: the
configuration file against the catalog row, the cell's traffic letter for
letter and its fit in the two cache groups, the parameter count and roofline
arithmetic against hand counts, the new reader on a canned span table, the
plain reference against itself (blocks of queries, the shares of a slice) and
its three controls, the borrowed run, and the rehearsal cell end to end on
the CPU (through ``run.py``, a process of its own). It pins MEMBERSHIP of
``BENCHMARK.json``'s lists, never a last place: the next configuration's
entries go behind this one's."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import roofline_mimo as rm
from benchmark import weights_mimo
from benchmark.reference import mimo_v2 as ref
from benchmark.traffic import shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "mixed_len_closed_sink"
SOURCE = ("https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/"
          "config.json")


def load(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


CONFIG = load("configs", "mimo-v2-flash-serve-ep16.json")
D = weights_mimo.dims_of(CONFIG)
TD = weights_mimo.dims_of(load("configs", "rehearsal-tiny-mimo.json"))
MIX = load("workloads", CELL + ".json")
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, source_url SOURCE), copied here: the guide is not part of a checkout
CATALOG = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000, "attention_bias": False,
    "v_head_dim": 128, "hybrid_layer_pattern": PATTERN,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192,
    "swa_v_head_dim": 128}


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, attrs):
    return {"name": name, "ts": 0.0, "dur": 0.001, "attrs": attrs,
            "events": []}


def groups(full, window, rows=True):
    extra = {"k_row_bytes": 1, "v_row_bytes": 1} if rows else {}
    return {"full": {"read": full, "live": full, **extra},
            "window": {"read": window, "live": window, **extra}}


# -- the configuration file and BENCHMARK.json ---------------------------------

def test_every_published_key_is_the_catalogs_except_the_three_reduced():
    assert CONFIG["source"] == SOURCE and CONFIG["system"] == "serve_mimo"
    assert CONFIG["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] \
        and entry["source"] == SOURCE \
        and entry["file"] == "benchmark/configs/" + CONFIG["name"] + ".json"
    for key, value in CATALOG.items():
        if key in ("n_routed_experts", "vocab_size"):
            assert CONFIG["published"][key] == value
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    # depth is cut beside the published key, as Laguna's and Granite's files
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"]) == (10, 48)
    assert (CONFIG["n_routed_experts"], CONFIG["experts_held"],
            CONFIG["vocab_size"]) == (16, [0, 16], 19072)
    assert 19072 * 8 == 152576
    # no width is reduced, in the list or out of it
    assert not any(w in k for k in CONFIG["reduced"]
                   for w in ("hidden", "intermediate", "head", "dim"))
    for key in ("published", "deployment", "assumed", "precision",
                "engine", "engine_note", "reduced_note"):
        assert CONFIG[key], key
    said = " ".join(CONFIG["assumed"])
    for phrase in ("pre-norm", "no Q/K norm", "value 1 = a sliding layer",
                   "joins the softmax's denominator", "float32 logit",
                   "attention_value_scale 0.707 multiplies v",
                   "int(0.334 * 192) = 64", "rotate-half",
                   "attention_chunk_size 128 is taken as the sliding window",
                   "e_bias normal std 0.01", "sinks normal std 1 about ln(128) + 1",
                   "std 0.02", "multi-token-prediction", "STORED 256 wide"):
        assert phrase in said, phrase
    # the guide's floors: a whole period, 8+ experts, 1/8 of the vocabulary
    assert D["L"] == 10 and D["dense"] == (0,)
    assert D["kinds"] == ("full",) + ("sliding",) * 4 + ("full",) \
        + ("sliding",) * 4
    assert D["count"] >= 8 and D["E"] == 256
    assert (D["full_rot"], D["swa_rot"], D["window"]) == (64, 64, 128)
    assert (D["full_kv"], D["swa_kv"], D["full_sink"], D["swa_sink"]) == (
        4, 8, False, True)


def test_the_benchmark_gains_one_configuration_one_cell_and_one_reader():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], CELL, 1)
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG["name"]] == [CELL]
    assert len(BENCH["workloads"]) >= 7
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(cell["why"]) <= 200 and "1.5 rows" in cell["why"]
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert by["sink_decode_roofline_share"] == {
        "name": "sink_decode_roofline_share", "unit": "%",
        "better": "higher", "source": "device_trace",
        "layer": "engine programs", "moves": "tpot_p95_ms",
        "workloads": [CELL]}
    assert hasattr(reader("sink_decode_roofline_share"), "read")
    # what reason_closed_kda reports of serving and of experts, it reports;
    # not what reads a state, another model's floor or the prefix cache
    apart = {"state_bytes_per_token", "kda_decode_roofline_share",
             "prefix_hit_share"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "reason_closed_kda" in m.get("workloads", []):
            assert (CELL in m["workloads"]) == (m["name"] not in apart), \
                m["name"]
    for name in apart:
        assert CELL not in by[name]["workloads"]
    # (``cache_bytes_per_token`` and ``swa_kv_bytes_read_per_token`` stay the
    # window cell's alone: tests/benchmark/test_swa.py pins their lists)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for name in ("serve_tok_per_s", "tpot_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    assert (e2e["serve_tok_per_s"]["bound"], e2e["tpot_p95_ms"]["bound"]) \
        == (0.05, 0.08)
    # Kimi's entries stand where they stood, this PR's behind them
    kimi = next(w for w in BENCH["workloads"]
                if w["name"] == "reason_closed_kda")
    assert kimi["config"] == "kimi-linear-48b-a3b-serve-ep16"
    assert by["kda_decode_roofline_share"]["workloads"] == [
        "reason_closed_kda"]
    for name in ("moe_expert_load_max_over_mean", "moe_held_pair_share"):
        assert by[name]["workloads"][-2:] == ["reason_closed_kda", CELL]
    assert by["state_bytes_per_token"]["workloads"][-1] \
        == "reason_closed_kda"


def test_an_earlier_prs_last_places_are_read_with_later_entries_cut_off(
        as_left_by):
    """``tests/conftest.py`` runs ``test_kda.py``'s last-place pins over
    this view: BENCHMARK.json less exactly what this PR appended."""
    assert as_left_by(BENCH, CELL) == BENCH
    was = as_left_by(BENCH, "reason_closed_kda")
    lists = ("configs", "workloads", "end_to_end", "per_layer")
    assert {k: v for k, v in was.items() if k not in lists} \
        == {k: v for k, v in BENCH.items() if k not in lists}
    assert was["workloads"] == BENCH["workloads"][:-1]
    assert was["configs"] == BENCH["configs"][:-1]
    assert BENCH["per_layer"][-1]["name"] == "sink_decode_roofline_share"
    for key, now in (("end_to_end", BENCH["end_to_end"]),
                     ("per_layer", BENCH["per_layer"][:-1])):
        assert len(was[key]) == len(now)
        for a, b in zip(was[key], now):
            listed = b.get("workloads", [])
            if CELL in listed:
                assert listed[-1] == CELL
                b = dict(b, workloads=listed[:-1])
            assert a == b


def test_traffic_is_the_issues_letter_for_letter_and_fits_both_groups():
    assert MIX["kind"] == "closed_loop" and MIX["clients"] == 48
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.1, "min": 128, "max": 8192}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.4, "min": 384, "max": 1536}
    assert (MIX["cycle"], MIX["pairing_seed"], MIX["ramp_s"],
            MIX["trace_s"]) == (16, 0, 16.0, 3.0)
    assert MIX["warmup"] == load("workloads",
                                 "reason_closed_kda.json")["warmup"]
    eng = CONFIG["engine"]
    assert eng == {"max_seqs": 48, "page_size": 16, "max_len": 9728,
                   "kv_dtype": "bf16", "prefill_chunk": 256,
                   "num_pages": 29185}
    check = load("checks", CELL + ".json")
    assert check["controls"] == ["fp8", "no_sink", "no_vscale"]
    assert set(check) == {"sample", "pad_to", "controls", "worst_gap_limit",
                          "argmax_share_min"}
    assert check["pad_to"] % ref.QUERY_BLOCK == 0
    prompts = shapes.cycle(MIX["prompt_len"], 16)
    outputs = shapes.cycle(MIX["output_len"], 16)
    assert (min(prompts), max(prompts)) == (132, 7946)
    assert sum(p < 1024 for p in prompts) == 8 \
        and sum(p > 4096 for p in prompts) == 2
    assert 1700 < sum(prompts) / 16 < 1780 and 800 < sum(outputs) / 16 < 850
    # every prompt over 256 tokens sends chunks twice the window
    assert sum(p > eng["prefill_chunk"] for p in prompts) == 14
    assert eng["prefill_chunk"] == 2 * CONFIG["sliding_window"]
    pair = shapes.rng(0, 7).permutation(16)
    longest = max(prompts[i] + outputs[int(pair[i])] for i in range(16))
    assert longest == 8790 <= check["pad_to"] <= eng["max_len"]
    assert max(prompts) + max(outputs) <= eng["max_len"]
    # every slot's longest sequence has its pages in both groups, every
    # caller its slot: nothing is truncated, nothing queues for a slot
    assert MIX["clients"] == eng["max_seqs"]
    assert eng["max_seqs"] * (eng["max_len"] // eng["page_size"]) \
        == eng["num_pages"] - 1
    assert rm.ring_pages(D, 16, 256) == 25


# -- sizes: parameters, pages, the roofline's arithmetic -----------------------

def test_parameter_count_and_page_bytes_are_the_issues():
    assert rm.attention_params(D, "full") == 55_574_528 + 33_554_432 \
        == 89_128_960
    assert rm.attention_params(D, "sliding") \
        == 60_817_408 + 33_554_432 + 64 == 94_371_904
    assert rm.expert_params(D) == 25_165_824
    assert rm.router_params(D) == 1_048_576 + 256
    assert rm.dense_params(D) == 201_326_592
    norms = 10 * 2 * 4096 + 4096
    assert weights_mimo.n_params(D) == rm.total_params(D) == (
        2 * 89_128_960 + 8 * 94_371_904 + 201_326_592
        + 9 * (16 * 25_165_824 + 1_048_832) + 2 * 19_072 * 4096 + norms) \
        == 4_924_201_728
    assert rm.weight_bytes(D) == pytest.approx(9.85e9, rel=1e-3)
    # a routed layer on this chip: 0.99 | 1.00 GB, as the issue reckons
    assert 2 * (89_128_960 + 1_048_832 + 16 * 25_165_824) \
        == pytest.approx(0.99e9, rel=5e-3)
    # a token's K and V at the published widths (what the floor counts) ...
    assert rm.row_bytes(D, "full") == {"k": 2 * 4 * 192 * 2,
                                       "v": 2 * 4 * 128 * 2}
    assert rm.row_bytes(D, "window") == {"k": 8 * 8 * 192 * 2,
                                         "v": 8 * 8 * 128 * 2}
    # ... the issue's count: 5,120 and 40,960 B a token
    assert rm.page_bytes(D, "full", 16) == 16 * 5_120 == 81_920
    assert rm.page_bytes(D, "window", 16) == 16 * 40_960 == 655_360
    # and AS STORED: a key 256 wide (192 in whole lanes)
    assert weights_mimo.key_width(D, "full") \
        == weights_mimo.key_width(D, "sliding") == 256
    assert rm.row_bytes(D, "full", stored=True) == {"k": 2 * 4 * 256 * 2,
                                                    "v": 2 * 4 * 128 * 2}
    assert rm.row_bytes(D, "window", stored=True) == {
        "k": 8 * 8 * 256 * 2, "v": 8 * 8 * 128 * 2}
    assert rm.page_bytes(D, "full", 16, stored=True) == 16 * 6_144 == 98_304
    assert rm.page_bytes(D, "window", 16, stored=True) \
        == 16 * 49_152 == 786_432
    full_pool = 29_185 * 98_304
    window_pool = (48 * 25 + 1) * 786_432
    assert full_pool == pytest.approx(2.87e9, rel=2e-3)
    assert window_pool == pytest.approx(0.944e9, rel=2e-3)
    total = rm.weight_bytes(D) + full_pool + window_pool
    assert 0.80 < total / 16_909_336_064 < 0.82


def test_roofline_counts_match_the_hand_counts():
    fixed = (2 * 89_128_960 + 8 * 94_371_904 + 201_326_592
             + 9 * 1_048_832 + 19_072 * 4096 + 21 * 4096)
    assert rm.fixed_params(D) == fixed
    assert rm.decode_tick_bytes(D, 0, 0, 0, 16) == 2 * fixed
    assert 2 * fixed == pytest.approx(2.45e9, rel=1e-2)
    # the issue's tick: 48 rows x 8 experts over 256 touch ~12.5 of 16 held
    # experts a layer; 48 rows of ~2k tokens, each 9 pages of window
    terms = rm.decode_tick_terms(D, 9 * 12.5, 48 * 128, 48 * 9, 16)
    assert terms == {"fixed_weights": 2 * fixed,
                     "experts_touched": 9 * 12.5 * 2 * 25_165_824,
                     "full_pages": 48 * 128 * 81_920,
                     "window_pages": 48 * 9 * 655_360}
    # the padding a stored key carries is no part of the floor: a program
    # that stops moving it reads a higher share
    stored = rm.stored_page_terms(D, 48 * 128, 48 * 9, 16)
    assert stored == {"full_pages": 48 * 128 * 98_304,
                      "window_pages": 48 * 9 * 786_432}
    assert all(stored[k] * 5 == terms[k] * 6 for k in stored)
    tick = rm.decode_tick_bytes(D, 9 * 12.5, 48 * 128, 48 * 9, 16)
    assert tick == sum(terms.values())
    assert 10.0 < tick / 819e9 * 1e3 < 12.0


# -- the reader ----------------------------------------------------------------

def test_roofline_share_reads_the_median_traced_decode_tick_and_says_its_terms():
    mod = reader("sink_decode_roofline_share")
    spans = []
    for seq, (full, window, touched) in enumerate(
            [(5000, 400, 100), (6144, 432, 112), (7000, 440, 120)]):
        spans.append(span("llm.issue.decode", {
            "issue_seq": seq, "kv_groups": groups(full, window)}))
        spans.append(span("llm.drain.emit", {"issue_seq": seq,
                                             "experts_touched": touched}))
    spans.append(span("llm.issue.mixed", {
        "issue_seq": 9, "kv_groups": groups(10 ** 6, 9000)}))
    want_ms = rm.decode_tick_bytes(D, 112, 6144, 432, 16) / 819e9 * 1e3
    said = []
    assert mod.compute(spans, D, 16, 2 * want_ms, 819e9, said.append) \
        == pytest.approx(50)
    (line,) = said
    terms = line["sink_decode_roofline_share"]
    assert terms["traced_decode_ticks"] == 3
    assert terms["median_tick_terms_bytes"] == rm.decode_tick_terms(
        D, 112, 6144, 432, 16)
    assert terms["median_tick_pages_bytes_as_stored"] \
        == rm.stored_page_terms(D, 6144, 432, 16)
    assert terms["floor_ms"] == pytest.approx(want_ms)
    # the parent (its groups say no ``k_row_bytes``), another model's
    # groups, a program with one pool: nothing, and no error
    for attrs in ({"kv_groups": groups(9, 9, rows=False), "issue_seq": 0},
                  {"kv_groups": {"latent": {"read": 1, "live": 1}},
                   "issue_seq": 0},
                  {"kv_pages_live": 9, "issue_seq": 0}):
        assert mod.compute(
            [span("llm.issue.decode", attrs),
             span("llm.drain.emit", {"issue_seq": 0, "experts_touched": 3})],
            D, 16, 30.0, 819e9) is None
    assert mod.compute(spans, D, 16, None, 819e9) is None
    assert mod.read({"dims": {"L": 24}, "peaks": {}}, None) is None
    assert mod.read({"dims": D, "peaks": None}, None) is None


# -- the weights and the reference ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return weights_mimo.make(TD, 2 ** 31 + 9, jnp.float32)


def test_weights_are_seeded_under_the_programs_names(tiny_params):
    again = weights_mimo.make(TD, 2 ** 31 + 9, jnp.float32)
    other = weights_mimo.make(TD, 2 ** 31 + 10, jnp.float32)
    name = "layers.2.moe.w_in"
    assert tiny_params[name].shape == (4, 64, 64)        # the held share
    assert tiny_params["layers.2.moe.router"].shape == (64, 8)
    assert np.array_equal(tiny_params[name], again[name])
    assert not np.array_equal(tiny_params[name], other[name])
    assert float(jnp.std(tiny_params[name])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(tiny_params["layers.2.moe.e_bias"])) < 0.03
    assert "layers.0.mlp.w_in.weight" in tiny_params \
        and "layers.0.moe.router" not in tiny_params
    # a full layer: 8 heads of 24 over 1 K/V head, values of 16, no sink; a
    # sliding one: 2 K/V heads and a sink a query head, of the size of a
    # score
    assert tiny_params["layers.0.attn.qkv_proj.weight"].shape == (
        64, 8 * 24 + 24 + 16)
    assert tiny_params["layers.1.attn.qkv_proj.weight"].shape == (
        64, 8 * 24 + 2 * (24 + 16))
    assert tiny_params["layers.1.attn.o_proj.weight"].shape == (8 * 16, 64)
    assert "layers.0.attn.sinks" not in tiny_params \
        and "layers.5.attn.sinks" not in tiny_params
    sinks = np.concatenate([np.asarray(tiny_params[f"layers.{l}.attn.sinks"])
                            for l in range(1, 5)])
    assert sinks.shape == (32,) and 0.5 < sinks.std() < 1.5
    assert TD["sink_mean"] == pytest.approx(np.log(8) + 1) \
        and abs(sinks.mean() - TD["sink_mean"]) < 0.6
    assert D["sink_mean"] == pytest.approx(5.852, abs=1e-3)
    bf16 = weights_mimo.make(TD, 1, jnp.bfloat16)
    for leaf in ("moe.e_bias", "attn.sinks"):
        assert bf16["layers.1." + leaf].dtype == jnp.float32
    assert bf16["layers.1.attn.qkv_proj.weight"].dtype == jnp.bfloat16
    assert sum(int(np.prod(v.shape)) for v in tiny_params.values()) \
        == weights_mimo.n_params(TD) == rm.total_params(TD)


def test_served_gaps_are_zero_for_the_references_own_tokens_and_every_control_is_not(
        tiny_params):
    ids = np.asarray(shapes.rng(3, 1).integers(0, TD["V"], (2, 64)),
                     np.int32)
    lg = ref.logits(tiny_params, ids, TD)
    own = np.zeros_like(ids)
    own[:, :-1] = np.argmax(np.asarray(lg), -1)[:, :-1]
    first, count = np.asarray([5, 9]), np.asarray([40, 50])
    controls = ("fp8", "no_sink", "no_vscale")
    got = ref.served_gaps(tiny_params, ids, first, count, own, TD, controls)
    assert int(got["mask"].sum()) == 90
    assert float(np.asarray(got["gap"]).max()) == 0.0
    for name in controls:
        assert float(np.asarray(got["control_gap"][name]).max()) > 1e-5
    plain = ref.served_gaps(tiny_params, ids, first, count, own, TD)
    assert plain["control_gap"] == {}


def test_reference_in_blocks_of_queries_is_the_reference_whole(
        tiny_params, monkeypatch):
    """64 positions in blocks of 8 queries with the window's 8 keys before
    each (the path 9k tokens take); later tokens move nothing before
    them."""
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


def test_the_references_sink_is_the_formula_by_hand():
    """One sliding layer's attention, a head at a time in float64, from the
    reference's own projections: the sink in the denominator and nowhere
    else, the window, the value scale."""
    d = dict(TD)
    lp = {k[len("layers.1."):]: jnp.asarray(v, jnp.float32) for k, v in
          weights_mimo.make(TD, 7, jnp.float32).items()
          if k.startswith("layers.1.")}
    u = jnp.asarray(shapes.rng(6, 1).normal(size=(20, 64)), jnp.float32)
    eye = dict(lp, **{"attn.o_proj.weight": jnp.eye(8 * 16)})
    got = np.asarray(ref.attention(u, eye, "sliding", ref._sizes(d)))
    qkv = np.asarray(u, np.float64) @ np.asarray(
        lp["attn.qkv_proj.weight"], np.float64)
    q, k, v = np.split(qkv, [8 * 24, 8 * 24 + 2 * 24], -1)
    inv = d["swa_theta"] ** (-np.arange(0, 8, 2) / 8.0)      # rot 8 of 24
    assert d["swa_rot"] == 8

    def rotate(x):
        x = x.copy()
        ang = np.arange(20)[:, None] * inv[None, :]
        x1, x2 = x[..., :4].copy(), x[..., 4:8].copy()
        x[..., :4] = x1 * np.cos(ang)[:, None] - x2 * np.sin(ang)[:, None]
        x[..., 4:8] = x2 * np.cos(ang)[:, None] + x1 * np.sin(ang)[:, None]
        return x

    q, k = rotate(q.reshape(20, 8, 24)), rotate(k.reshape(20, 2, 24))
    v = 0.707 * v.reshape(20, 2, 16)
    b = np.asarray(lp["attn.sinks"], np.float64)
    want = np.zeros((20, 8, 16))
    for p in range(20):
        lo = max(0, p - 8 + 1)
        for h in range(8):
            s = k[lo:p + 1, h // 4] @ q[p, h] / np.sqrt(24)
            m = max(s.max(), b[h])
            e = np.exp(s - m)
            want[p, h] = (e / (e.sum() + np.exp(b[h] - m))) \
                @ v[lo:p + 1, h // 4]
    np.testing.assert_allclose(got, want.reshape(20, -1), atol=2e-6)
    for quant, moved in (("no_sink", True), ("no_vscale", True)):
        other = np.asarray(ref.attention(u, eye, "sliding", ref._sizes(d),
                                         quant))
        assert (np.abs(other - got).max() > 1e-3) == moved


def test_the_references_shares_of_a_slice_sum_to_the_uncut_routed_layer(
        tiny_params):
    """The reference's own routed sum over experts 2-5 (what the rehearsal
    configuration holds) plus the other four's is the sum over all eight:
    all scored, ``top_k`` taken, the held ones add."""
    whole = weights_mimo.make(dict(TD, first=0, count=8), 2 ** 31 + 9,
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
    assert tiny_params["layers.2.moe.w_in"].shape[0] == 4
    assert TD["routed_scale"] == 1.0 and D["routed_scale"] == 1.0


# -- the check, the borrowed run, the rehearsal cell ----------------------------

def gaps_reading(worst, share, n=3000):
    """``n`` gaps of which ``share`` are 0 and the largest is ``worst``."""
    gaps = np.zeros(n, np.float32)
    miss = n - int(round(share * n))
    gaps[:miss] = np.linspace(worst, worst * 1e-3, miss)
    return gaps


# the chip's readings of the cell (PERF.md sections 4 and 6, my chip runs,
# PR 42, seeds 3142000101-106 and 201-206 with the controls, then the
# committed files alone cold, 3142000301, and six traced, 401-406): (worst
# gap, share of served tokens that are the reference's best), the program's
# and each control's
SOUND = [(0.2794, 0.9667), (0.1855, 0.9689), (0.2178, 0.9665),
         (0.1630, 0.9618), (0.1550, 0.9646), (0.1812, 0.9605),
         (0.2222, 0.9627), (0.2286, 0.9568), (0.1831, 0.9668),
         (0.2095, 0.9634), (0.2186, 0.9593), (0.2039, 0.9632),
         (0.2450, 0.9595), (0.2006, 0.9671), (0.1450, 0.9626),
         (0.1424, 0.9639), (0.1606, 0.9622), (0.3147, 0.9612),
         (0.1872, 0.9622),
         # the review round's tree (seeds 3142000501-503)
         (0.2089, 0.9655), (0.1896, 0.9626), (0.1801, 0.9671)]
CONTROLS = {
    "fp8": [(1.165, 0.641), (1.140, 0.645), (1.100, 0.652), (0.909, 0.647),
            (0.939, 0.637), (1.075, 0.658), (0.961, 0.652), (1.278, 0.639),
            (1.183, 0.630), (1.066, 0.659), (0.924, 0.652), (1.080, 0.641),
            (0.993, 0.635), (0.993, 0.651)],
    "no_sink": [(2.290, 0.395), (2.367, 0.401), (2.001, 0.415),
                (2.064, 0.383), (2.331, 0.389), (2.682, 0.376),
                (2.098, 0.387), (2.461, 0.403), (2.335, 0.383),
                (2.302, 0.367), (2.121, 0.374), (2.046, 0.396),
                (2.325, 0.399), (2.256, 0.396)],
    "no_vscale": [(1.421, 0.592), (1.352, 0.578), (1.442, 0.582),
                  (1.274, 0.581), (1.467, 0.552), (1.247, 0.559),
                  (1.411, 0.574), (1.357, 0.601), (1.432, 0.570),
                  (1.390, 0.551), (1.364, 0.588), (1.214, 0.578),
                  (1.243, 0.578), (1.436, 0.578)]}
# with the sinks drawn about 0 (the issue's letter) the sink is 0.5% of a
# window's denominator and the reference WITHOUT it reads as sound (seed
# 3000000017): why they are drawn about ln(window) + 1
SINK_ABOUT_ZERO = {"sound": (0.1547, 0.9608), "no_sink": (0.3099, 0.9535)}


def test_the_limits_lie_between_the_readings_with_room_on_both_sides():
    from benchmark.systems import serve_mimo
    spec = load("checks", CELL + ".json")
    lim, share = spec["worst_gap_limit"], spec["argmax_share_min"]
    for worst, sh in SOUND:
        assert serve_mimo.held(gaps_reading(worst, sh), spec)["correct"]
    # 22 sound runs (one more at the earlier draw of the sinks, below): the
    # largest leaves more than a third of the limit free
    assert len(SOUND) >= 20 and max(w for w, _ in SOUND) < lim * 2 / 3
    assert min(s for _, s in SOUND) - share > 0.1
    for name in spec["controls"]:
        for worst, sh in CONTROLS[name]:
            assert not serve_mimo.held(gaps_reading(worst, sh),
                                       spec)["correct"]
            # by EITHER number alone
            assert worst > 1.5 * lim and sh < share - 0.19
    # the draw about 0 could not be told from sound by any limit between
    sound, blind = SINK_ABOUT_ZERO["sound"], SINK_ABOUT_ZERO["no_sink"]
    assert serve_mimo.held(gaps_reading(*blind), spec)["correct"]
    assert abs(sound[1] - blind[1]) < 0.01


@pytest.mark.parametrize("cell", [CELL, "rehearsal_mimo"])
def test_the_check_holds_two_numbers_and_either_alone_refuses(cell):
    from benchmark.systems import serve_mimo
    spec = load("checks", cell + ".json")
    lim, share = spec["worst_gap_limit"], spec["argmax_share_min"]
    above = 1 - (1 - share) / 2
    got = serve_mimo.held(gaps_reading(lim * 0.9, above), spec)
    assert got["correct"]
    assert not serve_mimo.held(gaps_reading(lim * 1.1, above),
                               spec)["correct"]
    assert not serve_mimo.held(gaps_reading(lim * 0.9, share - 0.05),
                               spec)["correct"]


def test_the_borrowed_run_is_serve_swas_over_this_modules_three_things():
    from benchmark.systems import serve_kda, serve_mimo, serve_swa
    assert serve_mimo._with is serve_kda._with
    swapped = {"weights_swa", "build_net", "check_served", "_StallWatch"}
    assert swapped <= set(serve_swa.run.__code__.co_names)
    run = serve_mimo._with(serve_swa.run, weights_swa=weights_mimo,
                           build_net=serve_mimo.build_net,
                           check_served=serve_mimo.check_served)
    assert run.__code__ is serve_swa.run.__code__
    assert run.__globals__["weights_swa"] is weights_mimo
    assert run.__globals__["check_served"] is serve_mimo.check_served
    assert serve_swa.run.__globals__["weights_swa"] is not weights_mimo
    # every key the constructor is handed is a key of the file
    assert set(serve_mimo.PUBLISHED_KEYS) <= set(CONFIG)
    for name in ("dims_of", "make", "n_params"):
        assert hasattr(weights_mimo, name)


def test_the_check_frees_the_watched_engines_pages_and_nothing_else():
    """``release_pools`` deletes the arrays of the pool the run's stall
    watch saw, by reference: not the weights, not a key, not another pool."""
    from types import SimpleNamespace
    from benchmark.systems import serve_mimo
    from paddle_tpu.inference.page_pool import CacheGroup, PagePool

    def pool():
        return PagePool([CacheGroup("full", 2, 1, 128, None, None, 16),
                         CacheGroup("window", 4, 2, 128, 8, None, 16, True)],
                        9, 4, 2, 8, "f32", 16)

    mine, other = pool(), pool()
    weight = jnp.ones((4, 4))
    assert issubclass(serve_mimo._Watch, serve_mimo.serve_swa._StallWatch)
    serve_mimo._Watch(SimpleNamespace(_pool=mine))
    assert serve_mimo._Watch.pool is mine
    stored = sum(g.page_bytes * g.num_pages for g in mine.groups)
    assert serve_mimo.release_pools() == stored > 0
    assert all(a.is_deleted() for g in mine.groups
               for a in (g.k_pages, g.v_pages))
    assert not any(a.is_deleted() for g in other.groups
                   for a in (g.k_pages, g.v_pages))
    assert not weight.is_deleted()
    # once: the pool is forgotten with its pages
    assert serve_mimo._Watch.pool is None and serve_mimo.release_pools() == 0


def test_rehearsal_cell_walks_the_mimo_driver_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearsal_mimo", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1", "--control", "1"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    for name in ("kv_read_amplification", "tick_live_rows_p50",
                 "moe_held_pair_share", "moe_expert_load_max_over_mean",
                 "host_turn_ms.decode"):
        assert name in line["metrics"], (name, line["metrics"])
    for name in ("prefix_hit_share", "state_bytes_per_token",
                 # no peaks off the TPU: the share of a floor is not read
                 "sink_decode_roofline_share"):
        assert name not in line["metrics"]
    assert 40 < line["metrics"]["moe_held_pair_share"]["value"] < 60
    check = next(l for l in lines if "check" in l)
    assert [c["quant"] for c in check["controls"]] == [
        "fp8", "no_sink", "no_vscale"]
    assert not any(c["correct"] for c in check["controls"])
    sizing = next(l["sizing"] for l in lines if "sizing" in l)
    # the engine's pools, two groups of K and V pages, and no other array
    assert check["pool_bytes_freed_before"] == sum(
        g["page_bytes"] * g["pages"] for g in sizing["cache_groups"])
    assert [(g["name"], g["head_dim"], g["v_head_dim"], g["sink"])
            for g in sizing["cache_groups"]] == [
        ("full", 128, 16, False), ("window", 128, 16, True)]
