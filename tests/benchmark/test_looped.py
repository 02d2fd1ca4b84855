"""The looped configuration's share of the benchmark: the configuration file
against the catalog row, the cell's traffic letter for letter and its fit in
the pool, the parameter count and roofline arithmetic against hand counts,
each new reader on canned span tables and counter deltas, the plain
reference against itself and its fp8 control, and the rehearsal cell end to
end on the CPU (through ``run.py``, a process of its own)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import roofline_looped as rl
from benchmark import weights_looped
from benchmark.reference import ouro_looped as ref
from benchmark.systems import serve_looped
from benchmark.traffic import shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "reason_closed_looped"


def load(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


CONFIG = load("configs", "ouro-2.6b-serve-bf16.json")
D = weights_looped.dims_of(CONFIG)
TD = weights_looped.dims_of(load("configs", "rehearsal-tiny-looped.json"))
MIX = load("workloads", CELL + ".json")
# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, source_url https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/
# config.json), copied here: the guide is not part of a checkout
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, attrs):
    return {"name": name, "ts": 0.0, "dur": 0.001, "parent_id": None,
            "attrs": attrs, "events": []}


# -- the configuration file and BENCHMARK.json ---------------------------------

def test_configuration_equals_the_catalog_row_key_for_key():
    for key, value in CATALOG.items():
        assert CONFIG[key] == value, key
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] \
        == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert CONFIG["reduced"] == entry["reduced"] == []
    assert CONFIG["system"] == "serve_looped"
    assert CONFIG["engine"] == {"max_seqs": 8, "page_size": 16,
                                "max_len": 640, "kv_dtype": "bf16",
                                "prefill_chunk": 128}
    assert CONFIG["pool"]["min_pages"] == 321
    assert any("from memory" in a for a in CONFIG["assumed"])
    assert sum(a.startswith(("no biases", "sandwich norm", "after layer 47",
                             "exit gate", "every pass keeps"))
               for a in CONFIG["assumed"]) == 5


def test_the_benchmark_gains_one_configuration_one_cell_and_three_readers():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], CELL, 1)
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG["name"]] == [CELL]
    new = {"looped_decode_roofline_share": ("%", "tpot_p95_ms"),
           "loop_steps_per_token": ("passes", "serve_tok_per_s"),
           "kv_bytes_read_per_token": ("bytes", "serve_tok_per_s")}
    by = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, moves) in new.items():
        m = by[name]
        assert (m["unit"], m["moves"], m["workloads"], m["layer"]) == (
            unit, moves, [CELL], "engine programs")
        assert hasattr(reader(name), "read")
    # what chat_closed reports, the new cell reports, prefix hits included
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "chat_closed" in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    assert CELL in by["prefix_hit_share"]["workloads"]


def test_traffic_is_the_issues_letter_for_letter_and_fits_the_pool():
    assert MIX["kind"] == "closed_loop" and MIX["clients"] == 8
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 32, "max": 192}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.5, "min": 64, "max": 448}
    assert (MIX["cycle"], MIX["pairing_seed"], MIX["ramp_s"], MIX["trace_s"],
            MIX["warmup"]["min_requests"], MIX["warmup"]["quiet_s"]) == (
        16, 0, 8.0, 1.0, 16, 3.0)
    assert MIX["prime"] == {"prompt_len": 64, "max_new_tokens": 16}
    check = load("checks", CELL + ".json")
    assert check["pad_to"] == 192 + 448 == CONFIG["engine"]["max_len"]
    assert check["sample"] == 8 and check["control"] == "fp8"
    # the longest shape x clients fits num_pages - 1: nothing is truncated
    eng = CONFIG["engine"]
    longest = max(shapes.cycle(MIX["prompt_len"], 16)) \
        + max(shapes.cycle(MIX["output_len"], 16))
    assert longest <= eng["max_len"]
    pages = -(-longest // eng["page_size"])
    assert MIX["clients"] == eng["max_seqs"]
    assert MIX["clients"] * pages <= CONFIG["pool"]["min_pages"] - 1
    assert eng["max_seqs"] * -(-eng["max_len"] // eng["page_size"]) \
        == CONFIG["pool"]["min_pages"] - 1


# -- sizes: parameters, pages, the roofline's arithmetic -----------------------

def test_parameter_count_and_page_bytes_are_the_issues():
    assert rl.layer_params(D) == 51_388_416
    assert weights_looped.n_params(D) == rl.total_params(D) == 2_667_974_657
    assert rl.cache_layers(D) == 192
    assert rl.page_bytes(D, 16) == 25_165_824
    assert rl.page_bytes(D, 16) / 16 == 1_572_864       # a token


def test_the_page_plan_takes_what_the_reserve_leaves_and_fails_under_321():
    eng, pool = CONFIG["engine"], CONFIG["pool"]
    limit, weights = 16_909_860_864, 5_336_000_000
    plan = serve_looped.plan_pages(D, eng, pool, limit, weights)
    assert plan["page_bytes"] == 25_165_824
    assert plan["num_pages"] == int(
        (0.90 * limit - weights - 1_800_000_000) // 25_165_824) >= 321
    with pytest.raises(RuntimeError, match="under the 321"):
        serve_looped.plan_pages(D, eng, pool, limit, weights + 10 ** 9)


def test_roofline_counts_match_the_hand_counts():
    stack = 48 * 51_388_416 * 2
    top = (2048 + 2049 + 2048 * 49152) * 2
    assert rl.decode_tick_bytes(D, 0, 16) == 4 * stack + top
    # the issue's tick: 24.3 ms of weights at 819 GB/s before any K/V
    assert rl.decode_tick_bytes(D, 0, 16) / 819e9 * 1e3 == pytest.approx(
        24.3, abs=0.1)
    # 8 rows of ~350 tokens: 22 pages each
    assert rl.decode_tick_bytes(D, 176, 16) == 4 * stack + top \
        + 176 * 25_165_824
    assert rl.decode_tick_bytes(D, 176, 16) / 819e9 * 1e3 == pytest.approx(
        29.7, abs=0.1)


# -- the readers ---------------------------------------------------------------

def test_roofline_share_reads_the_median_traced_decode_tick():
    mod = reader("looped_decode_roofline_share")
    ours = {"loop_steps": 4, "kv_cache_layers": 192}
    spans = [span("llm.issue.decode", dict(ours, kv_pages_live=n))
             for n in (150, 176, 200)]
    spans.append(span("llm.issue.mixed", dict(ours, kv_pages_live=900)))
    want_ms = rl.decode_tick_bytes(D, 176, 16) / 819e9 * 1e3
    assert mod.compute(spans, D, 16, 2 * want_ms, 819e9) == pytest.approx(50)
    # a program that stamps no loop (the parent, another model): nothing
    assert mod.compute([span("llm.issue.decode", {"kv_pages_live": 9})], D,
                       16, 30.0, 819e9) is None
    assert mod.compute(spans, D, 16, None, 819e9) is None
    assert mod.read({"dims": {"L": 24}, "peaks": {}}, None) is None


def test_loop_steps_per_token_reads_the_windows_delta():
    mod = reader("loop_steps_per_token")
    assert mod.compute({"n_tokens": 100, "loop_steps": 400},
                       {"n_tokens": 1100, "loop_steps": 4400}) == 4.0
    assert mod.compute({"n_tokens": 5, "loop_steps": 0},
                       {"n_tokens": 5, "loop_steps": 0}) is None
    assert mod.read({"before": {"n_prompt_tokens": 1},
                     "after": {"n_prompt_tokens": 2}}, None) is None


def test_kv_bytes_read_per_token_prices_a_page_at_all_its_cache_layers():
    mod = reader("kv_bytes_read_per_token")
    spans = [span("llm.issue.decode", {"kv_pages_read": 176}),
             span("llm.issue.mixed", {"kv_pages_read": 24}),
             span("llm.drain.emit", {"tokens": 8}),
             span("llm.drain.emit", {"tokens": 2}),
             span("llm.loop.admit", {"kv_pages_read": 10 ** 9})]
    assert mod.compute(spans, 25_165_824) == 20 * 25_165_824
    assert mod.compute(spans, None) is None
    assert mod.compute([span("llm.drain.emit", {"tokens": 5})], 1) is None


# -- the weights and the reference ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return weights_looped.make(TD, 2 ** 31 + 9, jnp.float32)


def test_weights_are_seeded_under_the_programs_names(tiny_params):
    again = weights_looped.make(TD, 2 ** 31 + 9, jnp.float32)
    other = weights_looped.make(TD, 2 ** 31 + 10, jnp.float32)
    name = "layers.2.mlp.gate_up.weight"
    assert np.array_equal(tiny_params[name], again[name])
    assert not np.array_equal(tiny_params[name], other[name])
    assert float(jnp.std(tiny_params[name])) == pytest.approx(0.02, rel=0.1)
    assert float(tiny_params["gate.bias"][0]) == 0.0
    assert float(tiny_params["layers.0.post_attn_norm.weight"].min()) == 1.0
    assert sum(int(np.prod(v.shape)) for v in tiny_params.values()) \
        == weights_looped.n_params(TD)


def test_served_gaps_are_zero_for_the_references_own_tokens_and_the_control_is_not(
        tiny_params):
    ids = np.asarray(shapes.rng(3, 1).integers(0, TD["V"], (2, 40)),
                     np.int32)
    lg = ref.logits(tiny_params, ids, TD)
    own = np.zeros_like(ids)
    own[:, :-1] = np.argmax(np.asarray(lg), -1)[:, :-1]
    first, count = np.asarray([5, 9]), np.asarray([20, 30])
    got = ref.served_gaps(tiny_params, ids, first, count, own, TD, "fp8")
    assert int(got["mask"].sum()) == 50
    assert float(np.asarray(got["gap"]).max()) == 0.0
    assert float(np.asarray(got["control_gap"]).max()) > 1e-3
    assert set(np.asarray(got["exit_step"]).ravel().tolist()) == {2}


def test_reference_passes_are_not_one_pass_and_later_tokens_move_nothing(
        tiny_params):
    ids = np.asarray(shapes.rng(4, 1).integers(0, TD["V"], (1, 24)),
                     np.int32)
    whole = ref.logits(tiny_params, ids, TD)
    once = ref.logits(tiny_params, ids, dict(TD, steps=1))
    assert float(jnp.abs(whole - once).max()) > 1e-3
    moved = ids.copy()
    moved[0, 20:] = (moved[0, 20:] + 1) % TD["V"]
    again = ref.logits(tiny_params, moved, TD)
    np.testing.assert_allclose(again[0, :20], whole[0, :20], atol=1e-6)


# -- the rehearsal cell, end to end --------------------------------------------

def test_rehearsal_cell_walks_the_looped_driver_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearsal_looped", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    for name in ("loop_steps_per_token", "kv_bytes_read_per_token",
                 "kv_read_amplification", "tick_live_rows_p50",
                 "prefix_hit_share"):
        assert name in line["metrics"], (name, line["metrics"])
    assert line["metrics"]["loop_steps_per_token"]["value"] == 3.0
    assert line["metrics"]["prefix_hit_share"]["value"] == 0.0
