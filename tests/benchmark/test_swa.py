"""The window / full attention configuration's share of the benchmark: the
configuration file against the catalog row, the cell's traffic letter for
letter and its fit in both cache groups, the parameter count and roofline
arithmetic against hand counts, each new reader on canned span tables, the
plain reference against itself (the sliced sliding path against the whole
one) and its fp8 control, and the rehearsal cell end to end on the CPU
(through ``run.py``, a process of its own)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import roofline_swa as rs
from benchmark import weights_swa
from benchmark.reference import laguna_swa as ref
from benchmark.traffic import shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "agent_closed_swa"
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"


def load(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


CONFIG = load("configs", "laguna-s-2.1-serve-ep8.json")
D = weights_swa.dims_of(CONFIG)
TD = weights_swa.dims_of(load("configs", "rehearsal-tiny-swa.json"))
MIX = load("workloads", CELL + ".json")
# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, source_url SOURCE), copied here: the guide is not part of a checkout
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
CUT = {"num_experts": 32, "vocab_size": 12544}


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, attrs):
    return {"name": name, "ts": 0.0, "dur": 0.001, "parent_id": None,
            "attrs": attrs, "events": []}


def groups(full, window, held=(0, 0)):
    return {"full": {"read": full[0], "live": full[1],
                     "bytes_held": held[0], "page_bytes": 196_608},
            "window": {"read": window[0], "live": window[1],
                       "bytes_held": held[1], "page_bytes": 589_824}}


# -- the configuration file and BENCHMARK.json ---------------------------------

def test_configuration_equals_the_catalog_row_but_for_the_stated_cuts():
    for key, value in CATALOG.items():
        assert CONFIG[key] == CUT.get(key, value), key
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256, "vocab_size": 100352}
    assert CONFIG["num_layers"] == 12 and CONFIG["experts_held"] == [0, 32]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert CONFIG["reduced"] == entry["reduced"] == [
        "num_layers", "num_experts", "vocab_size"]
    assert CONFIG["system"] == "serve_swa"
    assert "v5e-32" in CONFIG["deployment"] \
        and "eight chips" in CONFIG["deployment"]
    assert sum(a.startswith(("pre-norm", "no Q/K norm", "gating per-head",
                             "the router's activation", "no gate on the",
                             "hidden_act silu", "YaRN", "seeded weights"))
               for a in CONFIG["assumed"]) == 8
    # the guide's floors: three whole periods, 8+ experts, 1/8 vocabulary
    assert D["kinds"].count("full_attention") == 3 \
        and D["kinds"].count("sliding_attention") == 9
    assert D["heads"] == (48, 72, 72, 72) * 3 and D["dense"] == (0,)
    assert D["count"] >= 8 and D["E"] == 256 and D["V"] * 8 == 100352


def test_the_benchmark_gains_one_configuration_one_cell_and_three_readers():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], CELL, 1)
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG["name"]] == [CELL]
    new = {"swa_decode_roofline_share":
           ("%", "tpot_p95_ms", "engine programs", "device_trace"),
           "cache_bytes_per_token":
           ("bytes", "serve_tok_per_s", "scheduler", "program_span"),
           "swa_kv_bytes_read_per_token":
           ("bytes", "serve_tok_per_s", "engine programs", "program_span")}
    by = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, moves, layer, source) in new.items():
        m = by[name]
        assert (m["unit"], m["moves"], m["layer"], m["source"],
                m["workloads"]) == (unit, moves, layer, source, [CELL])
        assert hasattr(reader(name), "read")
    # what chat_closed_hybrid reports of serving and of experts, it reports
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "chat_closed" in m.get("workloads", []) \
                and m["name"] != "prefix_hit_share":
            assert CELL in m["workloads"], m["name"]
    for name in ("moe_expert_load_max_over_mean", "moe_held_pair_share"):
        assert CELL in by[name]["workloads"]
    # the prefix cache is off for a window group: nothing to read there
    assert CELL not in by["prefix_hit_share"]["workloads"]


def test_traffic_is_the_issues_letter_for_letter_and_fits_both_groups():
    assert MIX["kind"] == "closed_loop" and MIX["clients"] == 32
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.7, "min": 512, "max": 8192}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.5, "min": 128, "max": 1024}
    assert (MIX["cycle"], MIX["pairing_seed"], MIX["ramp_s"],
            MIX["warmup"]["min_requests"]) == (16, 0, 16.0, 32)
    assert MIX["prime"] == {"prompt_len": 64, "max_new_tokens": 16}
    eng = CONFIG["engine"]
    assert eng == {"max_seqs": 32, "page_size": 16, "max_len": 9216,
                   "kv_dtype": "bf16", "prefill_chunk": 256,
                   "num_pages": 18433}
    check = load("checks", CELL + ".json")
    assert check["pad_to"] == eng["max_len"] and check["control"] == "fp8"
    assert check["pad_to"] % ref.QUERY_BLOCK == 0
    prompts = shapes.cycle(MIX["prompt_len"], 16)
    assert 540 < min(prompts) < 580 and 7400 < max(prompts) < 7600
    longest = max(prompts) + max(shapes.cycle(MIX["output_len"], 16))
    assert longest <= eng["max_len"]
    # every slot's longest sequence fits the full group, every slot's ring
    # the window group: nothing is truncated, whatever the pairing
    pages = eng["max_len"] // eng["page_size"]
    assert MIX["clients"] == eng["max_seqs"]
    assert eng["max_seqs"] * pages == eng["num_pages"] - 1
    assert rs.ring_pages(D, 16, eng["prefill_chunk"]) == 49


# -- sizes: parameters, pages, the roofline's arithmetic -----------------------

def test_parameter_count_and_page_bytes_are_the_issues():
    full = [l for l in range(12) if D["kinds"][l] == "full_attention"]
    assert rs.attention_params(D, full[0]) == 44_187_648
    assert rs.attention_params(D, 1) == 63_135_744
    assert rs.dense_params(D) == 113_246_208
    assert rs.expert_params(D) == 9_437_184
    assert 32 * rs.expert_params(D) + rs.shared_params(D) \
        + rs.router_params(D) == 312_213_504
    norms = 12 * 2 * 3072 + 3072
    assert weights_swa.n_params(D) == rs.total_params(D) == (
        3 * 44_187_648 + 9 * 63_135_744 + 113_246_208 + 11 * 312_213_504
        + 2 * 12_544 * 3072 + norms) == 4_325_526_528
    assert rs.weight_bytes(D) == pytest.approx(8.65e9, rel=1e-3)
    assert rs.page_bytes(D, "full", 16) == 196_608
    assert rs.page_bytes(D, "window", 16) == 589_824
    # the issue's pools: 3.62 GB and 0.93 GB; on one lifetime 14.5 GB
    assert 18_433 * 196_608 == pytest.approx(3.62e9, rel=2e-3)
    assert (32 * 49 + 1) * 589_824 == pytest.approx(0.93e9, rel=6e-3)
    assert 18_433 * (196_608 + 589_824) == pytest.approx(14.5e9, rel=2e-3)


def test_roofline_counts_match_the_hand_counts():
    fixed = (3 * 44_187_648 + 9 * 63_135_744 + 113_246_208
             + 11 * (3 * 3072 * 1024 + 3072 * 256) + 12_544 * 3072
             + 25 * 3072)
    assert rs.fixed_params(D) == fixed
    assert rs.decode_tick_bytes(D, 0, 0, 0, 16) == 2 * fixed
    # the issue's tick: 32 rows x 10 experts over 256 touch ~23 of 32 held
    # experts a layer; 32 rows of ~3k tokens; 33 window pages a row
    tick = rs.decode_tick_bytes(D, 11 * 23, 32 * 192, 32 * 33, 16)
    assert tick == 2 * fixed + 11 * 23 * 2 * 9_437_184 \
        + 32 * 192 * 196_608 + 32 * 33 * 589_824
    assert 9.0 < tick / 819e9 * 1e3 < 11.5
    # a token at 3k context: the weights held here and its attention
    flops = rs.token_flops(D, 3072)
    assert flops == 2.0 * (fixed + 11 * 10 * 32 / 256 * 9_437_184
                           + 3 * 2 * 48 * 128 * 3072
                           + 9 * 2 * 72 * 128 * 512)
    assert rs.token_flops(D, 100) < rs.token_flops(D, 512)


# -- the readers ---------------------------------------------------------------

def test_roofline_share_reads_the_median_traced_decode_tick_by_group():
    mod = reader("swa_decode_roofline_share")
    spans = []
    for seq, (full, window, touched) in enumerate(
            [(5000, 1000, 240), (6144, 1056, 253), (7000, 1056, 260)]):
        spans.append(span("llm.issue.decode", {
            "issue_seq": seq, "kv_groups": groups((full, full),
                                                  (window, window))}))
        spans.append(span("llm.drain.emit", {"issue_seq": seq,
                                             "experts_touched": touched}))
    spans.append(span("llm.issue.mixed", {
        "issue_seq": 9, "kv_groups": groups((10 ** 6, 9000), (9000, 9000))}))
    want_ms = rs.decode_tick_bytes(D, 253, 6144, 1056, 16) / 819e9 * 1e3
    assert mod.compute(spans, D, 16, 2 * want_ms, 819e9) == pytest.approx(50)
    # a program whose pool is one group (the parent, another model): nothing
    assert mod.compute([span("llm.issue.decode", {"kv_pages_live": 9,
                                                  "issue_seq": 0})],
                       D, 16, 30.0, 819e9) is None
    assert mod.compute(spans, D, 16, None, 819e9) is None
    assert mod.read({"dims": {"L": 24}, "peaks": {}}, None) is None


def test_cache_bytes_per_token_sums_the_groups_over_the_contexts():
    mod = reader("cache_bytes_per_token")
    spans = [span("llm.issue.decode", {
                 "kv_groups": groups((0, 0), (0, 0), held=(300, 100)),
                 "context_tokens": 40}),
             span("llm.issue.mixed", {
                 "kv_groups": groups((0, 0), (0, 0), held=(500, 300)),
                 "context_tokens": 60}),
             span("llm.issue.decode", {"kv_pages_live": 7}),
             span("llm.drain.emit", {"tokens": 8})]
    assert mod.compute(spans) == 1200 / 100
    assert mod.compute(spans[2:]) is None
    # one lifetime for all twelve layers would cost a token 49,152 B
    assert (196_608 + 589_824) / 16 == 49_152


def test_swa_kv_bytes_read_per_token_prices_a_page_at_its_groups_bytes():
    mod = reader("swa_kv_bytes_read_per_token")
    spans = [span("llm.issue.decode", {"kv_groups": groups((6144, 6144),
                                                           (1056, 1056))}),
             span("llm.issue.mixed", {"kv_groups": groups((50_000, 300),
                                                          (8448, 49))}),
             span("llm.drain.emit", {"tokens": 32}),
             span("llm.drain.emit", {"tokens": 8}),
             span("llm.issue.decode", {"kv_pages_read": 10 ** 9})]
    assert mod.compute(spans) == (56_144 * 196_608 + 9504 * 589_824) / 40
    assert mod.compute([span("llm.drain.emit", {"tokens": 5})]) is None
    assert mod.compute([span("llm.issue.decode", {"kv_pages_read": 5}),
                        span("llm.drain.emit", {"tokens": 5})]) is None


# -- the weights and the reference ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return weights_swa.make(TD, 2 ** 31 + 9, jnp.float32)


def test_weights_are_seeded_under_the_programs_names(tiny_params):
    again = weights_swa.make(TD, 2 ** 31 + 9, jnp.float32)
    other = weights_swa.make(TD, 2 ** 31 + 10, jnp.float32)
    name = "layers.2.moe.w_in"
    assert tiny_params[name].shape == (4, 64, 64)        # the held share
    assert tiny_params["layers.2.moe.router"].shape == (64, 8)
    assert np.array_equal(tiny_params[name], again[name])
    assert not np.array_equal(tiny_params[name], other[name])
    assert float(jnp.std(tiny_params[name])) == pytest.approx(0.02, rel=0.1)
    assert float(tiny_params["layers.0.post_norm.weight"].min()) == 1.0
    assert "layers.0.mlp.w_in.weight" in tiny_params \
        and "layers.0.moe.router" not in tiny_params
    assert tiny_params["layers.1.attn.g_proj.weight"].shape == (64, 6)
    assert sum(int(np.prod(v.shape)) for v in tiny_params.values()) \
        == weights_swa.n_params(TD) == rs.total_params(TD)


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
    assert float(np.asarray(got["control_gap"]).max()) > 1e-3


def test_reference_in_blocks_of_queries_is_the_reference_whole(
        tiny_params, monkeypatch):
    """64 positions in blocks of 8 queries: the full layers' blocks see
    every key, the sliding layers' the 24 before the block and its own (the
    path 9k tokens take); later tokens move nothing; the window cuts."""
    ids = np.asarray(shapes.rng(4, 1).integers(0, TD["V"], (1, 64)),
                     np.int32)
    whole = ref.logits(tiny_params, ids, TD)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    blocks = ref.logits(tiny_params, ids, TD)
    np.testing.assert_allclose(blocks, whole, atol=2e-6, rtol=2e-6)
    wide = ref.logits(tiny_params, ids, dict(TD, window=64))
    assert float(jnp.abs(wide - whole)[0, 30:].max()) > 1e-3
    np.testing.assert_allclose(wide[0, :24], whole[0, :24], atol=2e-6)
    moved = ids.copy()
    moved[0, 50:] = (moved[0, 50:] + 1) % TD["V"]
    again = ref.logits(tiny_params, moved, TD)
    np.testing.assert_allclose(again[0, :50], blocks[0, :50], atol=1e-6)


def test_the_references_shares_of_a_stage_sum_to_the_uncut_routed_layer(
        tiny_params):
    """The reference's own routed sum over experts 2-5 (what this
    configuration holds) plus the other four's is the sum over all eight."""
    lp = {k[len("layers.2."):]: v for k, v in tiny_params.items()
          if k.startswith("layers.2.")}
    whole = weights_swa.make(dict(TD, first=0, count=8), 2 ** 31 + 9,
                             jnp.float32)
    wp = {k[len("layers.2."):]: v for k, v in whole.items()
          if k.startswith("layers.2.")}
    x = jnp.asarray(shapes.rng(5, 1).normal(size=(1, 16, 64)), jnp.float32)
    parts = sum(ref.routed(x, dict(wp, **{
        "moe.w_in": wp["moe.w_in"][f:f + c],
        "moe.w_out": wp["moe.w_out"][f:f + c]}), TD, held=(f, c))
        for f, c in ((0, 2), (2, 4), (6, 2)))
    np.testing.assert_allclose(parts, ref.routed(x, wp, TD, held=(0, 8)),
                               atol=2e-6, rtol=2e-6)
    assert lp["moe.w_in"].shape[0] == 4


# -- the rehearsal cell, end to end --------------------------------------------

def test_rehearsal_cell_walks_the_swa_driver_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearsal_swa", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    for name in ("cache_bytes_per_token", "swa_kv_bytes_read_per_token",
                 "kv_read_amplification", "tick_live_rows_p50",
                 "moe_held_pair_share"):
        assert name in line["metrics"], (name, line["metrics"])
    assert "prefix_hit_share" not in line["metrics"]
    # a token of context costs less than a page's share in every layer
    assert line["metrics"]["cache_bytes_per_token"]["value"] \
        < (4096 + 6144) / 8 * 1.2
