"""The hybrid configuration's share of the benchmark: the plain reference
against itself and its fp8 control, the roofline's arithmetic against hand
counts, each new reader on canned span tables and counter deltas, the
configuration file's held and published counts, and the rehearsal cell end to
end on the CPU (through ``run.py``, a process of its own)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import roofline_hybrid as rh
from benchmark import weights_hybrid
from benchmark.reference import granite_hybrid as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "granite-4.0-h-small-serve-ep2.json")))
D = weights_hybrid.dims_of(CONFIG)
TINY = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "rehearsal-tiny-hybrid.json")))
TD = weights_hybrid.dims_of(TINY)


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, attrs):
    return {"name": name, "ts": 0.0, "dur": 0.001, "parent_id": None,
            "attrs": attrs, "events": []}


# -- the configuration file ---------------------------------------------------

def test_configuration_keeps_every_published_width_and_states_its_cut():
    catalog = {"hidden_size": 4096, "intermediate_size": 768,
               "shared_intermediate_size": 1536, "num_attention_heads": 32,
               "num_key_value_heads": 8, "mamba_n_heads": 128,
               "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
               "mamba_expand": 2, "mamba_n_groups": 1,
               "mamba_chunk_size": 256, "num_experts_per_tok": 10,
               "num_hidden_layers": 40, "logits_scaling": 16,
               "embedding_multiplier": 12, "residual_multiplier": 0.22,
               "attention_multiplier": 0.0078125, "rms_norm_eps": 1e-5,
               "max_position_embeddings": 131072,
               "position_embedding_type": "nope",
               "tie_word_embeddings": True}
    for key, value in catalog.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_layers", "num_local_experts",
                                 "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["num_local_experts"],
            CONFIG["vocab_size"]) == (10, 36, 50176)
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "num_local_experts": 72,
                                   "vocab_size": 100352}
    assert CONFIG["experts_held"] == [0, 36]
    assert len(CONFIG["layer_types"]) == 40
    assert D["kinds"] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert D["E"] == 72 and D["count"] == 36 and D["top_k"] == 10
    assert "v5e-8" in CONFIG["deployment"]
    assert CONFIG["precision"]["ssm_state"] == "float32"
    assert CONFIG["engine"] == {"max_seqs": 64, "page_size": 16,
                                "max_len": 2048, "kv_dtype": "bf16",
                                "prefill_chunk": 256, "num_pages": 8193}
    # the floors of a cut: a whole period, >= 8 experts, >= 1/8 vocabulary
    assert CONFIG["num_local_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= 100352


def test_traffic_is_the_issues_letter_for_letter():
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", "chat_closed_hybrid.json")))
    assert mix["kind"] == "closed_loop" and mix["clients"] == 64
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 16, "max": 384}
    assert (mix["cycle"], mix["ramp_s"], mix["trace_s"],
            mix["warmup"]["min_requests"]) == (16, 16.0, 6.0, 64)
    check = json.load(open(os.path.join(
        ROOT, "benchmark", "checks", "chat_closed_hybrid.json")))
    assert check["pad_to"] == 1024 + 384 and check["control"] == "fp8"


# -- the roofline's arithmetic -------------------------------------------------

def test_roofline_counts_match_the_hand_counts():
    assert rh.mixer_params(D, "mamba") == 102_286_976
    assert rh.mixer_params(D, "attention") == 41_943_040
    assert rh.shared_params(D) == 18_874_368
    assert rh.router_params(D) == 294_912
    assert rh.expert_params(D) == 9_437_184
    assert rh.total_params(D) == weights_hybrid.n_params(D)
    assert rh.weight_bytes(D) == pytest.approx(9.51e9, rel=2e-3)
    assert rh.ssm_state_bytes_per_row(D) == 9 * 128 * 64 * 128 * 4
    assert rh.ssm_state_bytes_per_row(D) == pytest.approx(37.7e6, rel=2e-3)
    assert rh.conv_state_bytes_per_row(D) == 9 * 3 * 8448 * 2
    assert rh.page_bytes(D, 16) == 64 * 1024
    # the issue's tick: all 36 experts of 10 layers, 64 rows, 64 x 2,048
    # tokens of pages: 11.6 ms of weights + 5.9 ms of state at 819 GB/s
    tick = rh.decode_tick_bytes(D, 360, 64, 0, 16)
    assert tick == rh.weight_bytes(D) + 2 * 64 * rh.state_bytes_per_row(D)
    assert tick / 819e9 * 1e3 == pytest.approx(17.6, abs=0.15)
    # an expert nobody was routed to is not read; a page is 64 KiB
    assert rh.decode_tick_bytes(D, 359, 64, 10, 16) == tick \
        - 2 * rh.expert_params(D) + 10 * 65536


# -- the readers ---------------------------------------------------------------

def test_roofline_share_reads_the_median_traced_decode_tick():
    mod = reader("hybrid_decode_roofline_share")
    spans = []
    for seq, (rows, touched) in enumerate([(64, 360), (64, 360), (32, 300)]):
        spans += [span("llm.issue.decode", {"issue_seq": seq,
                                            "state_rows": rows,
                                            "kv_pages_live": 100}),
                  span("llm.drain.emit", {"issue_seq": seq, "tokens": rows,
                                          "experts_touched": touched})]
    spans.append(span("llm.issue.mixed", {"issue_seq": 9, "state_rows": 70}))
    want_ms = rh.decode_tick_bytes(D, 360, 64, 100, 16) / 819e9 * 1e3
    assert mod.compute(spans, D, 16, 2 * want_ms, 819e9) == pytest.approx(50)
    # a program that stamps nothing (the parent), or no decode_fn: nothing
    assert mod.compute([span("llm.issue.decode", {"issue_seq": 1})], D, 16,
                       30.0, 819e9) is None
    assert mod.compute(spans, D, 16, None, 819e9) is None
    assert mod.read({"dims": {"L": 24}, "peaks": {}}, None) is None


def test_expert_load_and_held_share_read_the_windows_delta():
    load = reader("moe_expert_load_max_over_mean")
    before = [[10, 10, 10, 10], [0, 0, 0, 0]]
    after = [[20, 20, 20, 20], [30, 10, 10, 10]]
    assert load.compute(before, after) == pytest.approx(30 / 15)
    assert load.compute(before, before) is None
    assert load.read({"before": {}, "after": {}}, None) is None
    share = reader("moe_held_pair_share")
    assert share.compute({"moe_pairs": 100, "moe_pairs_held": 60},
                         {"moe_pairs": 1100, "moe_pairs_held": 560}) == 50.0
    assert share.read({"before": {"n_prompt_tokens": 1},
                       "after": {"n_prompt_tokens": 2}}, None) is None


def test_state_bytes_per_token_sums_issue_phases_over_emitted_tokens():
    mod = reader("state_bytes_per_token")
    spans = [span("llm.issue.decode", {"state_bytes": 4000}),
             span("llm.issue.mixed", {"state_bytes": 5000}),
             span("llm.drain.emit", {"tokens": 60}),
             span("llm.drain.emit", {"tokens": 30}),
             span("llm.loop.admit", {"state_bytes": 10 ** 9})]
    assert mod.compute(spans) == 100.0
    assert mod.compute([span("llm.issue.decode", {"state_bytes": 0}),
                        span("llm.drain.emit", {"tokens": 5})]) is None


# -- the reference -------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return weights_hybrid.make(TD, 2 ** 31 + 9, jnp.float32)


def test_weights_are_seeded_and_the_decays_lie_where_a_trained_models_do(
        tiny_params):
    again = weights_hybrid.make(TD, 2 ** 31 + 9, jnp.float32)
    other = weights_hybrid.make(TD, 2 ** 31 + 10, jnp.float32)
    name = "layers.1.moe.w_in"
    assert (tiny_params[name] == again[name]).all()
    assert not (tiny_params[name] == other[name]).all()
    assert sum(v.size for v in tiny_params.values()) \
        == weights_hybrid.n_params(TD)
    dt = np.log1p(np.exp(np.asarray(tiny_params["layers.0.mixer.dt_bias"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    a = np.exp(np.asarray(tiny_params["layers.0.mixer.A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert "layers.2.mixer.qkv_proj.weight" in tiny_params


def test_served_gaps_are_zero_for_the_references_own_tokens_and_the_control_is_not(
        tiny_params):
    # larger matrices than std 0.02 gives at width 64, so that tokens vary
    params = {k: (v * 6.0 if v.ndim >= 2 and "conv" not in k else v)
              for k, v in tiny_params.items()}
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, TD["V"], (3, 48)), jnp.int32)
    best = jnp.argmax(ref.logits(params, ids, TD), -1).astype(jnp.int32)
    first = jnp.asarray([5, 10, 20], jnp.int32)
    count = jnp.asarray([30, 20, 10], jnp.int32)
    out = ref.served_gaps(params, ids, first, count, best, TD, "fp8")
    assert int(out["mask"].sum()) == 60
    assert float(out["gap"].max()) == 0.0
    wrong = best.at[1, 15].set((best[1, 15] + 1) % TD["V"])
    bad = ref.served_gaps(params, ids, first, count, wrong, TD)
    assert float(bad["gap"][1, 15]) == float(bad["gap"].max()) > 0.0
    # the control: float8 operands move the logits (of up to 0.02 here) by
    # a thousand times the 2e-6 the program is held to against this
    # reference at this size (tests/test_granite_hybrid.py), and never read
    # better than the best
    assert float(out["control_gap"].min()) >= 0.0
    moved = jnp.abs(ref.logits(params, ids, TD, "fp8")
                    - ref.logits(params, ids, TD))
    assert float(moved.max()) > 2e-3


def test_reference_scans_token_by_token_from_a_zero_state(tiny_params):
    """A sequence's logits do not depend on what follows it (causal, state
    from the left only), and differ once what precedes it differs."""
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, TD["V"], (1, 24)), jnp.int32)
    whole = ref.logits(tiny_params, ids, TD)
    head = ref.logits(tiny_params, ids[:, :11], TD)
    np.testing.assert_allclose(whole[:, :11], head, atol=1e-6, rtol=1e-5)
    moved = ref.logits(tiny_params, ids.at[0, 0].add(1) % TD["V"], TD)
    assert float(jnp.abs(moved[0, 23] - whole[0, 23]).max()) > 0.0


# -- the rehearsal cell, end to end --------------------------------------------

def test_rehearsal_cell_walks_the_hybrid_driver_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearsal_hybrid", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    for name in ("moe_expert_load_max_over_mean", "moe_held_pair_share",
                 "state_bytes_per_token", "kv_read_amplification",
                 "tick_live_rows_p50"):
        assert name in line["metrics"], (name, line["metrics"])
    assert 30 < line["metrics"]["moe_held_pair_share"]["value"] < 70
