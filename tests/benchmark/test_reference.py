"""The plain reference against the program at a tiny size on the CPU, the
control (the reference at the next lower precision) read as not correct, and
a run with the timed path broken underneath coming out ``correct: false``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import gpt_dense
from benchmark.systems import serve, train

TINY = {"num_layers": 2, "hidden_size": 64, "num_heads": 4, "head_dim": 16,
        "ffn_hidden_size": 256, "max_position_embeddings": 128,
        "vocab_size": 512, "layer_norm_epsilon": 1e-5}
HP = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
      "weight_decay": 0.01}
D = weights.dims_of(TINY)


@pytest.fixture(scope="module")
def params():
    return weights.make(D, 2**31 + 9, jnp.float32)


def ids_of(seed, b=2, s=48):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, D["V"], (b, s)), jnp.int32)


def test_weights_are_seeded_stacked_or_not(params):
    again = weights.make(D, 2**31 + 9, jnp.float32)
    stacked = weights.make(D, 2**31 + 9, jnp.float32, stacked=True)
    other = weights.make(D, 2**31 + 10, jnp.float32)
    name = "gpt.layers.1.mlp.fc_out.weight"
    assert (params[name] == again[name]).all()
    assert (params[name] == stacked["layers.mlp.fc_out.weight"][1]).all()
    assert not (params[name] == other[name]).all()
    assert sum(v.size for v in params.values()) == weights.n_params(D)
    assert float(jnp.std(params[name])) == pytest.approx(0.02 / 2, rel=0.05)


def test_reference_logits_and_loss_match_the_program(params):
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    net = serve.build_net(TINY, params, use_flash=False)
    net.eval()
    ids = ids_of(0)
    logits = net(ids)
    top, x = gpt_dense.hidden_by_layer(params, ids, D)
    ref = gpt_dense.head(top, x, D["eps"])
    assert float(jnp.abs(logits - ref).max()) < 1e-5
    stacked = weights.make(D, 2**31 + 9, jnp.float32, stacked=True)
    assert float(gpt_dense.loss_stacked(stacked, ids, D)) == pytest.approx(
        float(GPTPretrainingCriterion()(logits, ids)), rel=1e-6)


def test_served_gaps_are_zero_for_the_references_own_tokens_and_the_control_is_not(
        params):
    ids = ids_of(1, b=3, s=64)
    top, x = gpt_dense.hidden_by_layer(params, ids, D)
    best = jnp.argmax(gpt_dense.head(top, x, D["eps"]), -1).astype(jnp.int32)
    first = jnp.asarray([10, 20, 30], jnp.int32)
    count = jnp.asarray([30, 20, 10], jnp.int32)
    out = gpt_dense.served_gaps(params, ids, first, count, best, D, "fp8")
    assert int(out["mask"].sum()) == 60
    assert float(out["gap"].max()) == 0.0
    # a token altered where it is served lies below the reference's best
    wrong = best.at[1, 25].set((best[1, 25] + 1) % D["V"])
    bad = gpt_dense.served_gaps(params, ids, first, count, wrong, D)
    assert float(bad["gap"].max()) > 0.0
    assert float(bad["gap"][1, 25]) == float(bad["gap"].max())
    # the control: float8 operands put another token first somewhere
    seeds_gap = []
    for seed in (2, 3, 4):
        ids = ids_of(seed, b=16, s=128)
        z = jnp.zeros(16, jnp.int32)
        c = gpt_dense.served_gaps(params, ids, z, z + 127, ids, D, "fp8")
        seeds_gap.append(float(c["control_gap"].max()))
    assert min(seeds_gap) > 0.0


def follow(quant, seed=5, steps=2):
    words = weights.key_words(seed)
    batches = [ids_of(100 + i, b=2, s=64) for i in range(steps)]
    return gpt_dense.train_reference(
        lambda: weights.make_stacked(D, words, jnp.float32), batches, D, HP,
        quant)


def test_training_control_fails_the_gradient_norm_and_sound_steps_do_not():
    from benchmark import stats
    ref, ctl = follow(None), follow("fp8")
    flat = lambda t: {f"{k}.{i}": float(x) for k, v in t.items()  # noqa: E731
                      for i, x in enumerate(np.atleast_1d(v))}
    low = stats.worst_leaf_gap(flat(ctl["grad_norms"]),
                               flat(ref["grad_norms"]))
    # float8 operands move a leaf's gradient norm by about a percent even at
    # this size; bfloat16's (what AMP O1 computes in) by some 1e-4
    assert low["gap"] > 0.003
    assert ref["losses"][0] == pytest.approx(np.log(D["V"]), rel=0.02)
    # a step that returns its state unchanged: the change norm gap is 1
    still = {k: 0.0 for k in flat(ref["change_norms"])}
    assert stats.worst_leaf_gap(still, flat(ref["change_norms"]))[
        "gap"] == pytest.approx(1.0)


class _Ctx:
    """What run.py hands a system driver, without the look for a chip."""

    def __init__(self, name, seed, seconds):
        here = os.path.dirname(os.path.abspath(__file__))
        bench = os.path.join(os.path.dirname(os.path.dirname(here)),
                             "benchmark")
        load = lambda *p: json.load(open(os.path.join(bench, *p)))  # noqa
        self.workload = load("workloads", name + ".json")
        self.config = load("configs", self.workload["config"] + ".json")
        self.check = load("checks", name + ".json")
        self.seed, self.seconds, self.trace, self.control = (
            seed, seconds, False, False)
        self.chips, self.bench_dir, self.lines = 1, bench, []
        self.say = self.lines.append
        self.memory_peak = None

    def mark(self, name):
        pass

    def window_opens(self):
        pass

    def read_memory_peak(self):
        pass

    def compile_count(self):
        return 0

    def compile_quiet_for(self):
        return 1e9


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    """One whole run at the rehearsal size, without the look for a chip, with
    the timed path broken underneath: the first gradient still agrees with
    the reference (that part of the run is sound and passes its limit), the
    parameters never move, and ``correct`` comes out false."""
    import paddle_tpu as pt
    real = pt.Model.train_batch

    def frozen(self, inputs, labels=None):
        self._sync_state_in()
        keep = jax.tree_util.tree_map(jnp.copy, self._params)
        logs = real(self, inputs, labels)
        self._params = keep
        return logs

    monkeypatch.setattr(pt.Model, "train_batch", frozen)
    ctx = _Ctx("rehearsal_train", 11, 0.3)
    broken = train.run(ctx)
    line = next(x for x in ctx.lines if "change_norm_gap" in x)
    assert line["grad_norm_gap"]["gap"] <= line["grad_norm_limit"]
    assert line["change_norm_gap"]["gap"] == pytest.approx(1.0)
    assert line["change_norm_gap"]["gap"] > line["change_norm_limit"]
    assert not broken["correct"], ctx.lines
