"""The gated-delta-rule / full-attention configuration's share of the
benchmark: the configuration file against the catalog row, the cell's traffic
letter for letter and its fit in the cache group and the state rows, the
parameter count and roofline arithmetic against hand counts, the new reader on
a canned span table, the plain reference against itself (blocks of queries and
of positions) and its controls, the borrowed run, and the rehearsal cell end to
end on the CPU (through ``run.py``, a process of its own). It pins MEMBERSHIP
of ``BENCHMARK.json``'s lists, never a last place: the next configuration's
entries go behind this one's."""
import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import roofline_olmo as ro
from benchmark import weights_olmo
from benchmark.reference import olmo_hybrid as ref
from benchmark.traffic import shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "reason_closed_gdn"
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
PARAMETERS = 2_435_748_072


def load(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


CONFIG = load("configs", "olmo-hybrid-7b-serve-pp4.json")
D = weights_olmo.dims_of(CONFIG)
TD = weights_olmo.dims_of(load("configs", "rehearsal-tiny-olmo.json"))
MIX = load("workloads", CELL + ".json")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, source_url SOURCE), copied here: the guide is not part of a checkout
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
SIX = ["chat_closed", "chat_closed_hybrid", "reason_closed_looped",
       "agent_closed_swa", "reason_closed_kda", "mixed_len_closed_sink"]


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, attrs):
    return {"name": name, "ts": 0.0, "dur": 0.001, "attrs": attrs,
            "events": []}


# -- the configuration file and BENCHMARK.json ---------------------------------

def test_every_published_key_is_the_catalogs_and_depth_alone_is_reduced():
    assert CONFIG["source"] == SOURCE and CONFIG["system"] == "serve_olmo"
    assert CONFIG["reduced"] == ["num_layers"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] \
        and entry["source"] == SOURCE \
        and entry["file"] == "benchmark/configs/" + CONFIG["name"] + ".json"
    for key, value in CATALOG.items():
        assert CONFIG[key] == value, key
    # depth is cut beside the published key, as the other files do
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["published"]) == (8, 32, {"num_hidden_layers": 32})
    for key in ("published", "deployment", "assumed", "precision",
                "engine", "engine_note", "reduced_note"):
        assert CONFIG[key], key
    said = " ".join(CONFIG["assumed"])
    for phrase in ("no norm before a branch, one on its output",
                   "the Olmo 2 / 3 order", "over the WHOLE projection",
                   "rope_theta null read as no rotation",
                   "ONE depthwise causal convolution", "without bias",
                   "then q / sqrt(96)", "ONE a head", "uniform(1, 16)",
                   "log-uniform in (0.001, 0.1)", "b = 2 sigmoid",
                   "weight of 192", "silu(x W_g)", "std 0.02",
                   "the residual stream float32"):
        assert phrase in said, phrase
    assert "v5e-4" in CONFIG["deployment"] \
        and "first stage" in CONFIG["deployment"] \
        and "absent, not simulated" in CONFIG["deployment"]
    # two whole periods at the published 3 : 1, every width as published
    assert D["L"] == 8 and D["kinds"] == tuple(PERIOD * 2)
    assert (D["H"], D["V"], D["F"], D["heads"], D["kv_heads"], D["hd"],
            D["lin_heads"], D["lin_k"], D["lin_v"], D["conv"],
            D["neg_eigval"]) == (3840, 100352, 11008, 30, 30, 128, 30, 96,
                                 192, 4, True)
    assert CONFIG["precision"]["weights"] == "bfloat16" \
        and CONFIG["precision"]["ssm_state"] == "float32"


def test_the_benchmark_gains_one_configuration_one_cell_and_one_reader():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], CELL, 1)
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG["name"]] == [CELL]
    assert len(BENCH["workloads"]) >= 8
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(cell["why"]) <= 200 and "64 slots" in cell["why"]
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert by["gdn_decode_roofline_share"] == {
        "name": "gdn_decode_roofline_share", "unit": "%",
        "better": "higher", "source": "device_trace",
        "layer": "engine programs", "moves": "tpot_p95_ms",
        "workloads": [CELL]}
    assert hasattr(reader("gdn_decode_roofline_share"), "read")
    # what every serving cell reports, it reports, and the state's bytes;
    # not the experts' counters, another model's floor or the prefix cache
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        listed = m.get("workloads", [])
        if all(c in listed for c in SIX):
            assert CELL in listed, m["name"]
    assert CELL in by["state_bytes_per_token"]["workloads"]
    for name in ("moe_expert_load_max_over_mean", "moe_held_pair_share",
                 "kda_decode_roofline_share", "sink_decode_roofline_share",
                 "prefix_hit_share", "cache_bytes_per_token"):
        assert CELL not in by[name]["workloads"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for name in ("serve_tok_per_s", "tpot_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    assert (e2e["serve_tok_per_s"]["bound"], e2e["tpot_p95_ms"]["bound"]) \
        == (0.05, 0.08)
    # the entries that were there stand where they stood, in their order
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index("mixed_len_closed_sink")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        listed = m.get("workloads", [])
        if CELL in listed and "mixed_len_closed_sink" in listed:
            assert listed.index(CELL) > listed.index("mixed_len_closed_sink")


def test_the_view_of_the_benchmark_as_the_pr_before_left_it_drops_this_prs_entries(
        as_left_by):
    """``tests/conftest.py`` runs ``test_mimo.py``'s last-place pins over
    this view: BENCHMARK.json less exactly what this PR appended (and what
    later PRs append behind it)."""
    mine = as_left_by(BENCH, CELL)
    was = as_left_by(BENCH, "mixed_len_closed_sink")
    lists = ("configs", "workloads", "end_to_end", "per_layer")
    assert {k: v for k, v in was.items() if k not in lists} \
        == {k: v for k, v in mine.items() if k not in lists}
    assert was["workloads"] == mine["workloads"][:-1]
    assert mine["workloads"][-1]["name"] == CELL
    assert was["configs"] == mine["configs"][:-1]
    assert mine["configs"][-1]["name"] == CONFIG["name"]
    gone = [m["name"] for m in mine["per_layer"]
            if m["name"] not in {w["name"] for w in was["per_layer"]}]
    assert gone == ["gdn_decode_roofline_share"]
    assert [m["name"] for m in was["end_to_end"]] \
        == [m["name"] for m in mine["end_to_end"]]
    kept = {m["name"]: m for m in mine["end_to_end"] + mine["per_layer"]}
    for a in was["end_to_end"] + was["per_layer"]:
        b = kept[a["name"]]
        listed = b.get("workloads", [])
        if CELL in listed:
            assert listed[-1] == CELL
            b = dict(b, workloads=listed[:-1])
        assert a == b, a["name"]
    assert was["per_layer"][-1]["name"] == "sink_decode_roofline_share"


def test_traffic_is_the_issues_letter_for_letter_and_fits_the_pool():
    assert MIX["kind"] == "closed_loop" and MIX["clients"] == 64
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 128, "max": 1536}
    assert MIX["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.4, "min": 512, "max": 2048}
    assert (MIX["cycle"], MIX["pairing_seed"], MIX["ramp_s"],
            MIX["trace_s"], MIX["timeout_s"], MIX["drain_s"]) == (
        16, 0, 16.0, 3.0, 900, 200)
    assert MIX["warmup"] == {"min_requests": 32, "quiet_s": 3.0,
                             "max_s": 1000}
    assert MIX["prime"] == load("workloads",
                                "reason_closed_kda.json")["prime"]
    assert "think_s" not in MIX and "shared_prefix" not in MIX
    eng = CONFIG["engine"]
    assert eng == {"max_seqs": 64, "page_size": 16, "max_len": 3584,
                   "kv_dtype": "bf16", "prefill_chunk": 256,
                   "num_pages": 14337}
    check = load("checks", CELL + ".json")
    assert check["controls"] == ["fp8", "beta_unscaled", "no_qk_norm"]
    assert check["reported"] == ["state_bf16"]
    assert set(check) == {"sample", "pad_to", "controls", "reported",
                          "worst_gap_limit", "argmax_share_min"}
    assert (check["sample"], check["pad_to"]) == (8, 3584)
    assert check["pad_to"] % ref.QUERY_BLOCK == 0
    prompts = shapes.cycle(MIX["prompt_len"], 16)
    outputs = shapes.cycle(MIX["output_len"], 16)
    assert 128 <= min(prompts) and max(prompts) <= 1536
    assert 512 <= min(outputs) and max(outputs) <= 2048
    assert 1000 < sum(outputs) / 16 < 1200 and 480 < sum(prompts) / 16 < 640
    # the longest request fits a slot's table and the check's padding:
    # nothing can be truncated or wait for a page
    assert max(prompts) + max(outputs) <= check["pad_to"] == eng["max_len"]
    assert MIX["clients"] == eng["max_seqs"]
    assert eng["max_seqs"] * (eng["max_len"] // eng["page_size"]) \
        == eng["num_pages"] - 1


# -- sizes: parameters, pages, state, the roofline's arithmetic ----------------

def test_parameter_count_state_and_page_bytes_are_the_issues():
    assert weights_olmo.n_params(D) == ro.total_params(D) == PARAMETERS
    assert ro.linear_params(D) == 88_750_332
    assert ro.layer_params(D, 0) == 215_570_172
    assert ro.layer_params(D, 3) == 185_809_920
    assert 6 * 215_570_172 + 2 * 185_809_920 + 2 * 100_352 * 3_840 + 3_840 \
        == PARAMETERS
    assert ro.weight_bytes(D) == 2 * PARAMETERS
    # the cache as written, and as stored
    assert ro.token_bytes(D) == 30_720
    assert ro.token_bytes(D, heads_stored=32) == 32_768
    assert ro.state_row_bytes(D) == 6 * (2_211_840 + 3 * 11_520 * 2) \
        == 13_685_760
    assert ro.state_row_bytes(D, value_width=256) \
        == 6 * (2_949_120 + 69_120) == 18_109_440
    assert weights_olmo.conv_width(D) == 11_520
    assert (ro.linear_layers(D), ro.full_layers(D)) == (6, 2)
    note = CONFIG["engine_note"]
    for said in ("14,337", "524,288", "2,949,120", "18,109,440"):
        assert said in note, said


def test_roofline_counts_match_the_hand_counts():
    terms = ro.decode_tick_terms(D, 64, 5000, 16)
    assert terms == {
        "weights": (PARAMETERS - 100_352 * 3_840) * 2,
        "state": 2.0 * 64 * 13_685_760,
        "pages": 5000 * 16 * 30_720}
    assert ro.decode_tick_bytes(D, 64, 5000, 16) == sum(terms.values())
    # the issue's tick: 64 rows at 1,250 tokens: 4.10 + 1.75 + 2.46 GB
    tick = ro.decode_tick_terms(D, 64, 64 * 1250 / 16, 16)
    assert [round(tick[k] / 1e9, 2) for k in ("weights", "state",
                                              "pages")] == [4.1, 1.75, 2.46]
    assert ro.decode_tick_bytes(D, 64, 5000, 16) / 819e9 * 1e3 \
        == pytest.approx(10.1, abs=0.2)
    # a token: the weights once, the step's four passes over [K, V] a
    # head, the attention's two products over the context
    assert ro.token_flops(D, 1000) == 2.0 * (
        PARAMETERS - 100_352 * 3_840 + 6 * 4 * 30 * 96 * 192
        + 2 * 2 * 30 * 128 * 1000)


def test_roofline_share_reads_the_median_traced_decode_tick_and_says_its_terms():
    mod = reader("gdn_decode_roofline_share")

    def full(live):
        return {"full": {"read": live, "live": live, "page_bytes": 524_288}}

    spans = [span("llm.issue.decode", {
        "issue_seq": seq, "state_rows": rows, "state_bytes": 2 * 65 * 18,
        "kv_groups": full(live)})
        for seq, (rows, live) in enumerate([(60, 4000), (64, 5000),
                                            (64, 6000)])]
    spans.append(span("llm.issue.mixed", {
        "issue_seq": 9, "state_rows": 70, "kv_groups": full(10 ** 6)}))
    want_ms = ro.decode_tick_bytes(D, 64, 5000, 16) / 819e9 * 1e3
    said = []
    assert mod.compute(spans, D, 16, 2 * want_ms, 819e9, said.append) \
        == pytest.approx(50)
    (line,) = said
    terms = line["gdn_decode_roofline_share"]
    assert terms["traced_decode_ticks"] == 3
    assert terms["median_tick_terms_bytes"] == ro.decode_tick_terms(
        D, 64, 5000, 16)
    # beside the floor's terms, what the program stores for the same tick
    assert terms["median_tick_bytes_as_stored"] == {
        "state": 2 * 65 * 18, "pages": 5000 * 524_288}
    assert terms["median_tick_bytes_as_stored"]["pages"] \
        > terms["median_tick_terms_bytes"]["pages"]
    assert terms["floor_ms"] == pytest.approx(want_ms)
    # it can never read over 100: a tick at its floor reads 100
    assert mod.compute(spans, D, 16, want_ms, 819e9) == pytest.approx(100)
    # the parent has no such model; another model's groups, a program
    # with one pool, a program without state rows: nothing, and no error
    for attrs in ({"kv_groups": {"latent": {"read": 1, "live": 1}},
                   "state_rows": 3},
                  {"kv_pages_live": 9, "state_rows": 3},
                  {"kv_groups": full(9)}):
        assert mod.compute([span("llm.issue.decode", attrs)], D, 16, 30.0,
                           819e9) is None
    assert mod.compute(spans, D, 16, None, 819e9) is None
    assert mod.read({"dims": {"L": 24}, "peaks": {}}, None) is None
    assert mod.read({"dims": D, "peaks": None}, None) is None


# -- the weights and the reference ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    return weights_olmo.make(TD, 2 ** 31 + 9, jnp.float32)


def test_weights_are_seeded_under_the_programs_names(tiny_params):
    again = weights_olmo.make(TD, 2 ** 31 + 9, jnp.float32)
    other = weights_olmo.make(TD, 2 ** 31 + 10, jnp.float32)
    name = "layers.1.mixer.qkv_proj.weight"
    assert tiny_params[name].shape == (48, 3 * (8 + 8 + 16))
    assert np.array_equal(tiny_params[name], again[name])
    assert not np.array_equal(tiny_params[name], other[name])
    assert float(jnp.std(tiny_params[name])) == pytest.approx(0.02, rel=0.1)
    # a full layer: 6 heads of 8 over 6 K/V heads, norms over the whole
    # projections; a rule layer: one decay and one strength a head
    assert tiny_params["layers.3.mixer.qkv_proj.weight"].shape == (48, 144)
    assert tiny_params["layers.3.mixer.q_norm.weight"].shape == (48,)
    assert tiny_params["layers.0.mixer.a_proj.weight"].shape == (48, 3)
    assert tiny_params["layers.0.mixer.A_log"].shape == (3,)
    assert tiny_params["layers.0.mixer.o_norm_weight"].shape == (16,)
    assert "layers.3.mixer.A_log" not in tiny_params
    a = np.exp(np.asarray(tiny_params["layers.0.mixer.A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    bf16 = weights_olmo.make(TD, 1, jnp.bfloat16)
    for leaf in ("mixer.A_log", "mixer.dt_bias"):
        assert bf16["layers.1." + leaf].dtype == jnp.float32
    assert bf16[name].dtype == jnp.bfloat16
    assert sum(int(np.prod(v.shape)) for v in tiny_params.values()) \
        == weights_olmo.n_params(TD) == ro.total_params(TD)
    # the program's own leaves, name for name
    import jax
    from benchmark.systems import serve_olmo
    net = serve_olmo.build_net(load("configs", "rehearsal-tiny-olmo.json"),
                               tiny_params)
    assert set(net.state_dict()) == set(tiny_params)
    assert all(isinstance(v, jax.Array) for v in net.state_dict().values())


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(ROOT, "benchmark", "reference",
                             "olmo_hybrid.py")).read()
    assert "paddle_tpu" not in text.replace("``paddle_tpu", "")
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert "lax.scan" in text and 'precision=HI' in text


def test_served_gaps_are_zero_for_the_references_own_tokens_and_every_control_is_not(
        tiny_params):
    scaled = {k: v * 8 if v.ndim >= 2 and "conv" not in k else v
              for k, v in tiny_params.items()}
    ids = np.asarray(shapes.rng(3, 1).integers(0, TD["V"], (2, 64)),
                     np.int32)
    lg = ref.logits(scaled, ids, TD)
    own = np.zeros_like(ids)
    own[:, :-1] = np.argmax(np.asarray(lg), -1)[:, :-1]
    first, count = np.asarray([5, 9]), np.asarray([40, 50])
    controls = ("fp8", "beta_unscaled", "no_qk_norm", "state_bf16")
    got = ref.served_gaps(scaled, ids, first, count, own, TD, controls)
    assert int(got["mask"].sum()) == 90
    assert float(np.asarray(got["gap"]).max()) == 0.0
    for name in controls:
        assert float(np.asarray(got["control_gap"][name]).max()) > 1e-5, name
    plain = ref.served_gaps(scaled, ids, first, count, own, TD)
    assert plain["control_gap"] == {}


def test_reference_in_blocks_is_the_reference_whole(tiny_params,
                                                    monkeypatch):
    """Blocks of queries in the attention and blocks of positions under the
    head: the same logits and gaps as one block."""
    ids = np.asarray(shapes.rng(4, 1).integers(0, TD["V"], (1, 64)),
                     np.int32)
    whole = ref.logits(tiny_params, ids, TD)
    served = np.asarray(shapes.rng(5, 1).integers(0, TD["V"], (1, 64)),
                        np.int32)
    first, count = np.asarray([3]), np.asarray([50])
    gaps = ref.served_gaps(tiny_params, ids, first, count, served, TD)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    import jax
    jax.clear_caches()
    np.testing.assert_allclose(ref.logits(tiny_params, ids, TD), whole,
                               atol=1e-6, rtol=1e-5)
    in_blocks = ref.served_gaps(tiny_params, ids, first, count, served, TD)
    assert np.array_equal(in_blocks["mask"], gaps["mask"])
    np.testing.assert_allclose(in_blocks["gap"], gaps["gap"], atol=1e-6)
    assert int(np.asarray(gaps["mask"]).sum()) == 50
    # by hand: best - served logit at a served position
    lg = np.asarray(whole)[0]
    t = 10
    assert float(np.asarray(gaps["gap"])[0, t]) == pytest.approx(
        lg[t].max() - lg[t, served[0, t]], abs=1e-6)


def test_the_references_rule_is_the_definition_token_by_token():
    """``delta_rule`` against a numpy loop of ``S = a (I - b k k^T) S + b k
    v^T``, ``o = S^T q``, with strengths up to 2 and a state that is not
    square."""
    r = np.random.default_rng(0)
    s, h, dk, dv = 9, 2, 4, 6
    q, k = r.normal(size=(2, s, h, dk)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(s, h, dv)).astype(np.float32)
    log_a = -r.uniform(0, 0.5, (s, h)).astype(np.float32)
    b = r.uniform(0, 2, (s, h)).astype(np.float32)
    got = np.asarray(ref.delta_rule(*map(jnp.asarray, (q, k, v, log_a, b))))
    state = np.zeros((h, dk, dv))
    for t in range(s):
        for j in range(h):
            kk = k[t, j]
            state[j] = np.exp(log_a[t, j]) * (
                np.eye(dk) - b[t, j] * np.outer(kk, kk)) @ state[j] \
                + b[t, j] * np.outer(kk, v[t, j])
            # (the decay is a scalar: it commutes with the projection)
            np.testing.assert_allclose(got[t, j], state[j].T @ q[t, j],
                                       atol=1e-5)


# -- the check ------------------------------------------------------------------

def gaps_reading(worst, share, n=3000):
    """``n`` gaps of which ``share`` are 0 and the largest is ``worst``."""
    gaps = np.zeros(n, np.float32)
    miss = n - int(round(share * n))
    gaps[:miss] = np.linspace(worst, worst * 1e-3, miss)
    return gaps


# the chip's readings of the cell (PERF.md section 4, my chip runs, PR 47:
# seed 3447000101 traced with the controls, 3447000201-206 (the first three
# with the controls) and 3447000301-306 untraced): (worst gap, share of
# served tokens that are the reference's best), the program's and each
# control's
SOUND = [
    (0.3581, 0.8602), (0.376, 0.8728), (0.2993, 0.8642), (0.3031, 0.8598),
    (0.3723, 0.863), (0.3453, 0.8625), (0.3004, 0.8684), (0.3214, 0.8665),
    (0.3277, 0.8561), (0.3667, 0.8629), (0.2794, 0.8638), (0.3986, 0.8597),
    (0.2901, 0.8627),
    # the revised tree (the stored heads in the model): seeds 3447000701
    # traced with the controls, 801-806 untraced
    (0.3319, 0.8586), (0.3656, 0.8612), (0.2946, 0.8658), (0.3301, 0.8601),
    (0.3053, 0.8631), (0.3312, 0.8612), (0.3866, 0.8657),
    # 901-906 untraced
    (0.3916, 0.8657), (0.3361, 0.8666), (0.3088, 0.8669), (0.2896, 0.8625),
    (0.3709, 0.8602), (0.3348, 0.8682)]
CONTROLS = {
    "fp8": [
        (4.811, 0.078), (4.867, 0.077), (4.635, 0.0796), (5.018, 0.0756),
        (5.447, 0.0818)],
    "beta_unscaled": [
        (8.246, 0.001), (8.165, 0.0032), (8.513, 0.0015), (8.048, 0.0019),
        (7.98, 0.0007)],
    "no_qk_norm": [
        (7.291, 0.0315), (6.088, 0.0332), (5.439, 0.0333), (5.787, 0.0309),
        (5.868, 0.0319)],
}
# read and reported, not required to fail: the rule's state rounded to
# bfloat16 after every token, in the reference
REPORTED = {
    "state_bf16": [
        (0.571, 0.7702), (0.444, 0.8196), (0.535, 0.7776), (0.51, 0.7903),
        (0.608, 0.7726)]}


def test_the_limits_lie_between_the_readings_with_room_on_both_sides():
    from benchmark.systems import serve_olmo
    spec = load("checks", CELL + ".json")
    lim, share = spec["worst_gap_limit"], spec["argmax_share_min"]
    assert len(SOUND) >= 12
    for worst, sh in SOUND:
        assert serve_olmo.held(gaps_reading(worst, sh), spec)["correct"]
    # at least 1.5 x of room to the nearest sound reading, by both numbers
    assert 1.5 * max(w for w, _ in SOUND) <= lim
    assert 1.5 * (1 - min(s for _, s in SOUND)) <= 1 - share
    for name in spec["controls"]:
        assert len(CONTROLS[name]) >= 3, name
        for worst, sh in CONTROLS[name]:
            # every control reading refused by at least one number
            assert not serve_olmo.held(gaps_reading(worst, sh),
                                       spec)["correct"], name
    # a state rounded to bfloat16 a token lies between: under the worst
    # gap's limit in every reading and about the share's (what holds a
    # state's precision is tests/test_olmo_hybrid.py and chip_smoke.py's
    # whole state arrays)
    assert len(REPORTED["state_bf16"]) >= 2
    assert all(w < lim and 0.7 < sh < min(s for _, s in SOUND)
               for w, sh in REPORTED["state_bf16"])


# ``held()``'s ``mean_gap`` in the same runs (my chip runs, PR 47: the 13 of
# SOUND's first hand-in and the committed files' traced run, the 13 of the
# revised tree; the 5 of REPORTED)
SOUND_MEAN_GAP = [
    0.00911, 0.00834, 0.00899, 0.00943, 0.00910, 0.00934, 0.00866, 0.00951,
    0.00975, 0.00909, 0.00901, 0.00971, 0.00914, 0.00899,
    0.00949, 0.00941, 0.00905, 0.00972, 0.00925, 0.00970, 0.00906,
    0.00870, 0.00920, 0.00872, 0.00893, 0.00961, 0.00908]
STATE_BF16_MEAN_GAP = [0.02693, 0.01710, 0.02670, 0.02226, 0.02727]


def test_no_third_number_separates_a_state_rounded_to_bfloat16():
    """Why ``state_bf16`` stays under ``reported``: the mean gap, the one
    number of ``held()`` that might have held the state's float32, reads a
    factor of 1.75 between the sound runs and the rounded state, and a limit
    with 1.5 x of room on both sides needs 2.25. ``correct`` does not hold
    the state's precision (PERF.md section 7 says who must)."""
    spec = load("checks", CELL + ".json")
    assert spec["reported"] == ["state_bf16"] \
        and "state_bf16" not in spec["controls"]
    assert len(SOUND_MEAN_GAP) >= 12
    ratio = min(STATE_BF16_MEAN_GAP) / max(SOUND_MEAN_GAP)
    assert 1.0 < ratio < 1.5 ** 2, ratio
    sound_miss = 1 - min(s for _, s in SOUND)
    rounded_miss = 1 - max(s for _, s in REPORTED["state_bf16"])
    assert 1.0 < rounded_miss / sound_miss < 1.5 ** 2


@pytest.mark.parametrize("cell", [CELL, "rehearsal_olmo"])
def test_the_check_holds_two_numbers_and_either_alone_refuses(cell):
    from benchmark.systems import serve_olmo
    spec = load("checks", cell + ".json")
    lim, share = spec["worst_gap_limit"], spec["argmax_share_min"]
    above = 1 - (1 - share) / 2
    assert serve_olmo.held(gaps_reading(lim * 0.9, above), spec)["correct"]
    assert not serve_olmo.held(gaps_reading(lim * 1.1, above),
                               spec)["correct"]
    assert not serve_olmo.held(gaps_reading(lim * 0.9, share - 0.05),
                               spec)["correct"]


def test_the_borrowed_run_is_serve_swas_over_this_modules_things():
    from benchmark.systems import serve_kda, serve_olmo, serve_swa
    assert serve_olmo._with is serve_kda._with \
        and serve_olmo.held is serve_kda.held
    swapped = {"weights_swa", "build_net", "check_served", "_StallWatch",
               "_snapshot_hybrid"}
    assert swapped <= set(serve_swa.run.__code__.co_names)
    run = serve_olmo._with(serve_swa.run, weights_swa=weights_olmo,
                           build_net=serve_olmo.build_net,
                           check_served=serve_olmo.check_served)
    assert run.__code__ is serve_swa.run.__code__
    assert run.__globals__["weights_swa"] is weights_olmo
    assert run.__globals__["check_served"] is serve_olmo.check_served
    assert serve_swa.run.__globals__["weights_swa"] is not weights_olmo
    # every key the constructor is handed is a key of the file
    assert set(serve_olmo.PUBLISHED_KEYS) <= set(CONFIG)
    assert set(serve_olmo.PUBLISHED_KEYS) == set(CATALOG) - {"model_type"}
    for name in ("dims_of", "make", "n_params"):
        assert hasattr(weights_olmo, name)
    # a dense model's snapshot: what the borrowed run subtracts, at 0
    from types import SimpleNamespace
    snap = serve_olmo._snapshot_dense(SimpleNamespace(
        n_host_dispatches=3, n_prompt_tokens=5, n_cached_tokens=0))
    assert snap["moe_pairs"] == 0 and snap["n_host_dispatches"] == 3


def test_the_check_frees_the_watched_engines_pages_and_state_and_nothing_else():
    """``release_cache`` deletes the page arrays and the state rows of the
    engine the run's stall watch saw, by reference: not the weights, not
    another pool."""
    from types import SimpleNamespace
    from benchmark.systems import serve_olmo
    from paddle_tpu.inference.page_pool import CacheGroup, PagePool

    def pool():
        return PagePool([CacheGroup("full", 2, 6, 8)], 9, 4, 2, 8, "f32",
                        16)

    mine, other = pool(), pool()
    conv = (jnp.ones((3, 3, 8)),) * 1
    ssm = (jnp.ones((3, 2, 4, 128)), jnp.ones((3, 2, 4, 128)))
    weight = jnp.ones((4, 4))
    assert issubclass(serve_olmo._Watch, serve_olmo.serve_swa._StallWatch)
    serve_olmo._Watch(SimpleNamespace(_pool=mine, conv_state=conv,
                                      ssm_state=ssm))
    stored = sum(g.page_bytes * g.num_pages for g in mine.groups) \
        + conv[0].nbytes + sum(a.nbytes for a in ssm)
    assert serve_olmo.release_cache() == stored > 0
    assert all(a.is_deleted() for g in mine.groups
               for a in (g.k_pages, g.v_pages))
    assert all(a.is_deleted() for a in conv + ssm)
    assert not any(a.is_deleted() for g in other.groups
                   for a in (g.k_pages, g.v_pages))
    assert not weight.is_deleted()
    # once: the engine is forgotten with its arrays
    assert serve_olmo._Watch.eng is None and serve_olmo.release_cache() == 0


def test_rehearsal_cell_walks_the_olmo_driver_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearsal_olmo", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1", "--control", "1"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    for name in ("kv_read_amplification", "tick_live_rows_p50",
                 "state_bytes_per_token", "host_turn_ms.decode"):
        assert name in line["metrics"], (name, line["metrics"])
    for name in ("prefix_hit_share", "moe_held_pair_share",
                 # no peaks off the TPU: the share of a floor is not read
                 "gdn_decode_roofline_share"):
        assert name not in line["metrics"]
    check = next(l for l in lines if "check" in l)
    assert [(c["quant"], c["required"]) for c in check["controls"]] == [
        ("fp8", True), ("beta_unscaled", True), ("no_qk_norm", True),
        ("state_bf16", False)]
    sizing = next(l["sizing"] for l in lines if "sizing" in l)
    (group,) = sizing["cache_groups"]
    # the heads a page STORES: the model's six and two of zeros
    assert (group["name"], group["kv_heads"]) == ("full", 8)
    assert sizing["parameters"] == weights_olmo.n_params(TD)
    # the engine's pages AS STORED and its state rows, and no other array
    state = (4 + 1) * 3 * (3 * 96 * 4 + 3 * 8 * 128 * 4)
    assert check["cache_bytes_freed_before"] \
        == group["page_bytes"] * group["pages"] + state
