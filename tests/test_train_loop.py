"""Fused multi-step train loop (ISSUE 3): `Model.fit(steps_per_loop=K)`
scans K optimizer steps inside ONE XLA dispatch, fed by double-buffered
[K, ...] superbatches. The pinned contract: the loss stream is
BIT-IDENTICAL to the K=1 path (per-step keys derived from the step
index inside the scan, exactly `rng.split_for_step`), metric coercion
defers to log/display boundaries, and the recompile guard counts one
signature per superbatch shape."""

import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.io import DataLoader, TensorDataset, stack_batches
from paddle_tpu.metric import Accuracy
from paddle_tpu.optimizer import Adam


def _make_model(metrics=(), dropout=0.0, seed=7, lr=1e-3):
    pt.seed(seed)
    layers = [nn.Flatten(), nn.Linear(12, 32), nn.ReLU()]
    if dropout:
        layers.append(nn.Dropout(dropout))
    layers.append(nn.Linear(32, 4))
    net = nn.Sequential(*layers)
    model = pt.Model(net)
    model.prepare(optimizer=Adam(learning_rate=lr, parameters=net),
                  loss=nn.CrossEntropyLoss(), metrics=list(metrics))
    return model


def _data(n=64, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 12).astype(np.float32)
    y = rs.randint(0, 4, n).astype(np.int64)
    return x, y


class _RecordLoss(Callback):
    def __init__(self):
        super().__init__()
        self.losses = []

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(float(logs["loss"]))


# ---------------------------------------------------------------------------
# bit-identical parity (the acceptance-pinned invariant)
# ---------------------------------------------------------------------------

def test_train_loop_batch_bit_identical_to_train_batch():
    x, y = _data(64)
    xs = x.reshape(8, 8, 12)
    ys = y.reshape(8, 8)

    m1 = _make_model()
    ref = [float(np.asarray(m1.train_batch([xs[i]], [ys[i]])["loss"]))
           for i in range(8)]

    m2 = _make_model()
    logs = m2.train_loop_batch([xs[:4]], [ys[:4]])
    logs += m2.train_loop_batch([xs[4:]], [ys[4:]])
    fused = [float(lg["loss"]) for lg in logs]

    assert ref == fused  # bitwise, not allclose
    # final state identical too (same donated-carry math)
    m1.sync_weights()
    m2.sync_weights()
    for (n1, v1), (n2, v2) in zip(
            sorted(m1.network.state_dict().items()),
            sorted(m2.network.state_dict().items())):
        assert n1 == n2
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    assert m1._step_count == m2._step_count == 8


def test_train_loop_rng_parity_with_dropout():
    """Per-step keys inside the scan must match rng.split_for_step —
    dropout makes a key mismatch show up in the loss stream."""
    x, y = _data(64)
    xs, ys = x.reshape(8, 8, 12), y.reshape(8, 8)
    m1 = _make_model(dropout=0.5)
    ref = [float(np.asarray(m1.train_batch([xs[i]], [ys[i]])["loss"]))
           for i in range(8)]
    m2 = _make_model(dropout=0.5)
    fused = [float(lg["loss"])
             for lg in m2.train_loop_batch([xs], [ys])]
    assert ref == fused


def test_fit_steps_per_loop_parity_and_ragged_tail():
    # 72 samples / batch 8 = 9 steps → K=4 slabs of 4 + 4 + 1 (tail
    # runs the per-step path)
    x, y = _data(72)
    ds = TensorDataset([x, y])

    rec1, rec4 = _RecordLoss(), _RecordLoss()
    m1 = _make_model()
    m1.fit(ds, batch_size=8, epochs=2, verbose=0, shuffle=False,
           callbacks=[rec1], steps_per_loop=1)
    m4 = _make_model()
    m4.fit(ds, batch_size=8, epochs=2, verbose=0, shuffle=False,
           callbacks=[rec4], steps_per_loop=4)

    assert len(rec1.losses) == len(rec4.losses) == 18
    assert rec1.losses == rec4.losses
    assert m1._step_count == m4._step_count == 18


def test_fit_steps_per_loop_flag_default():
    from paddle_tpu.core import flags
    x, y = _data(32)
    ds = TensorDataset([x, y])
    rec1, recf = _RecordLoss(), _RecordLoss()
    m1 = _make_model()
    m1.fit(ds, batch_size=8, epochs=1, verbose=0, shuffle=False,
           callbacks=[rec1])
    flags.set_flags({"steps_per_loop": 4})
    try:
        mf = _make_model()
        mf.fit(ds, batch_size=8, epochs=1, verbose=0, shuffle=False,
               callbacks=[recf])  # no explicit arg: flag drives K
    finally:
        flags.set_flags({"steps_per_loop": 1})
    assert rec1.losses == recf.losses
    # the flag-driven run dispatched slabs: its only signature is the
    # [4, ...] loop program
    assert mf.compiled_shape_count == 1
    assert m1.compiled_shape_count == 1


def test_fit_steps_per_loop_learns():
    """The fused path trains for real: LeNet-free tiny problem must
    still converge through slab dispatches."""
    rs = np.random.RandomState(3)
    y = rs.randint(0, 4, 256)
    x = (np.eye(4, 12, dtype=np.float32)[y] * 3.0
         + rs.randn(256, 12).astype(np.float32) * 0.1)
    ds = TensorDataset([x, y.astype(np.int64)])
    m = _make_model(metrics=[Accuracy()], lr=1e-2)
    m.fit(ds, batch_size=32, epochs=8, verbose=0, shuffle=True,
          steps_per_loop=4)
    res = m.evaluate(ds, batch_size=32, verbose=0)
    assert res["acc"] > 0.9, res


# ---------------------------------------------------------------------------
# recompile guard accounting (satellite)
# ---------------------------------------------------------------------------

def test_guard_one_signature_per_superbatch_shape():
    x, y = _data(64)
    xs, ys = x.reshape(8, 8, 12), y.reshape(8, 8)
    m = _make_model()
    for _ in range(3):
        m.train_loop_batch([xs[:4]], [ys[:4]])
    assert m.compiled_shape_count == 1  # same slab shape = one program
    m.train_loop_batch([xs[:2]], [ys[:2]])
    assert m.compiled_shape_count == 2  # new K = new signature
    m.train_batch([xs[0]], [ys[0]])
    # K=1 step program counted consistently, as its own signature
    assert m.compiled_shape_count == 3


def test_guard_cap_holds_for_loop_signatures():
    m = _make_model()
    x, y = _data(16)
    xs, ys = x.reshape(2, 8, 12), y.reshape(2, 8)
    m._shape_signatures = {("pad", i) for i in range(4096)}
    m.train_loop_batch([xs], [ys])
    assert m.compiled_shape_count == 4096  # bounded at the cap
    m.train_batch([x[:8]], [y[:8]])
    assert m.compiled_shape_count == 4096


# ---------------------------------------------------------------------------
# superbatch iterator (io)
# ---------------------------------------------------------------------------

def test_superbatches_stacks_and_flushes_ragged_tail():
    x = np.arange(72, dtype=np.float32).reshape(72, 1)
    y = np.arange(72, dtype=np.int64)
    dl = DataLoader(TensorDataset([x, y]), batch_size=8, shuffle=False,
                    to_device=False)
    slabs = list(dl.superbatches(4))
    assert [s[0].shape for s in slabs] == [(4, 8, 1), (4, 8, 1), (1, 8, 1)]
    np.testing.assert_array_equal(slabs[0][1][1],
                                  np.arange(8, 16))  # order preserved
    np.testing.assert_array_equal(slabs[2][1][0], np.arange(64, 72))


def test_superbatches_flushes_on_shape_change():
    # 20 samples / batch 8, drop_last=False → 8, 8, 4: the short final
    # batch cannot stack with the full ones and must flush the slab
    x = np.arange(20, dtype=np.float32).reshape(20, 1)
    dl = DataLoader(TensorDataset([x]), batch_size=8, shuffle=False,
                    to_device=False)
    slabs = list(dl.superbatches(4))
    assert [s[0].shape for s in slabs] == [(2, 8, 1), (1, 4, 1)]


def test_superbatches_device_prefetch():
    import jax
    x = np.arange(32, dtype=np.float32).reshape(32, 1)
    dl = DataLoader(TensorDataset([x]), batch_size=8, shuffle=False)
    slabs = list(dl.superbatches(2))
    assert all(isinstance(s[0], jax.Array) for s in slabs)


def test_stack_batches_structure():
    a = (np.ones((2, 3)), np.zeros(2))
    b = (np.full((2, 3), 2.0), np.ones(2))
    out = stack_batches([a, b])
    assert out[0].shape == (2, 2, 3)
    np.testing.assert_array_equal(out[1], [[0, 0], [1, 1]])


# ---------------------------------------------------------------------------
# deferred metric coercion (satellite)
# ---------------------------------------------------------------------------

def test_metric_update_deferred_until_display():
    x, y = _data(32)
    acc = Accuracy()
    m = _make_model(metrics=[acc])
    logs = m.train_batch([x[:8]], [y[:8]])
    logs2 = m.train_batch([x[8:16]], [y[8:16]])
    # no host coercion yet: the accumulator has seen nothing
    assert acc.count == 0
    v = float(logs2["acc"])  # display boundary → drain
    assert acc.count == 16  # both buffered steps folded in
    assert 0.0 <= v <= 1.0
    # draining is idempotent
    assert float(logs["acc"]) == v


def test_metric_values_match_eager_reference():
    x, y = _data(64)
    xs, ys = x.reshape(8, 8, 12), y.reshape(8, 8)

    # eager reference: update per step, read after 8 steps
    ref_acc = Accuracy()
    m1 = _make_model(metrics=[ref_acc])
    for i in range(8):
        logs = m1.train_batch([xs[i]], [ys[i]])
    ref = float(logs["acc"])

    fused_acc = Accuracy()
    m2 = _make_model(metrics=[fused_acc])
    logs = m2.train_loop_batch([xs], [ys])
    got = float(logs[-1]["acc"])
    assert got == ref
    assert fused_acc.count == ref_acc.count == 64


def test_lazy_log_values_behave_like_floats():
    """Old contract: logs carried plain floats — callbacks doing
    comparisons/arithmetic on metric entries must keep working."""
    x, y = _data(32)
    m = _make_model(metrics=[Accuracy()])
    logs = m.train_loop_batch([x.reshape(4, 8, 12)], [y.reshape(4, 8)])[-1]
    acc, loss = logs["acc"], logs["loss"]
    assert (acc > -1.0) and (acc <= 1.0)
    assert acc * 2 == 2 * float(acc)
    assert 1.0 - acc == pytest.approx(1.0 - float(acc))
    assert loss > 0.0
    assert f"{acc:.4f}" == f"{float(acc):.4f}"
    assert round(acc, 4) == round(float(acc), 4)
    assert int(loss) == int(float(loss))


def test_drain_metrics_public_api_and_boundary_semantics():
    """Manual eval_batch loops read accumulate() after drain_metrics();
    evaluate()/fit() fold still-buffered outputs BEFORE resetting, so
    Metric state at every boundary matches immediate-update semantics;
    a log value coerced at its display boundary memoizes and survives a
    later reset."""
    x, y = _data(32)
    acc = Accuracy()
    m = _make_model(metrics=[acc])
    for i in range(2):
        m.eval_batch([x[i * 16:(i + 1) * 16]], [y[i * 16:(i + 1) * 16]])
    assert acc.count == 0  # deferred
    m.drain_metrics()
    assert acc.count == 32  # public drain folds everything

    logs = m.train_batch([x[:16]], [y[:16]])
    train_acc = float(logs["acc"])  # display boundary → memoized
    m.evaluate(TensorDataset([x, y]), batch_size=16, verbose=0)
    assert float(logs["acc"]) == train_acc  # reset doesn't corrupt it


def test_pending_metric_buffer_is_bounded():
    """Nothing displaying (verbose=0 loops) must not pile up unbounded
    device buffers: the pending list auto-drains at the cap."""
    x, y = _data(16)
    acc = Accuracy()
    m = _make_model(metrics=[acc])
    for _ in range(m._PENDING_DRAIN_CAP + 10):
        m.train_batch([x], [y])
    assert len(m._metric_pending) <= m._PENDING_DRAIN_CAP
    assert acc.count > 0  # the backstop drain actually folded updates


def test_eval_metrics_drained_by_evaluate():
    x, y = _data(64)
    acc = Accuracy()
    m = _make_model(metrics=[acc])
    res = m.evaluate(TensorDataset([x, y]), batch_size=16, verbose=0)
    assert acc.count == 64
    assert res["acc"] == pytest.approx(acc.accumulate())


def test_update_stacked_matches_per_step_updates():
    rs = np.random.RandomState(0)
    correct = rs.rand(4, 8, 1) > 0.5  # [K, batch, topk] compute output
    a1, a2 = Accuracy(), Accuracy()
    for i in range(4):
        a1.update(correct[i])
    a2.update_stacked((correct,), nsteps=4)
    assert a1.count == a2.count
    assert a1.accumulate() == a2.accumulate()


# ---------------------------------------------------------------------------
# distributed composition (shard_superbatch)
# ---------------------------------------------------------------------------

def test_train_loop_parity_under_data_parallel_mesh():
    """The fused loop composes with DistributedModel: superbatches are
    sharded on dim 1 (batch) over the dp axis while dim 0 (steps) stays
    replicated for the scan — losses must still match the sharded K=1
    path bitwise."""
    from paddle_tpu import parallel
    from paddle_tpu.distributed import fleet

    x, y = _data(128)
    ds = TensorDataset([x, y])
    streams = []
    for k in (1, 4):
        fleet.init(is_collective=True)
        try:
            m = _make_model()
            fleet.distributed_model(m)
            assert m._shard_superbatch is not None
            rec = _RecordLoss()
            m.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False,
                  callbacks=[rec], steps_per_loop=k)
            streams.append(rec.losses)
        finally:
            parallel.set_mesh(None)
    assert len(streams[0]) == len(streams[1]) == 8
    assert streams[0] == streams[1]


# ---------------------------------------------------------------------------
# observability + compilation cache (satellites)
# ---------------------------------------------------------------------------

def test_train_loop_metrics_registered():
    from paddle_tpu import observability as obs
    x, y = _data(32)
    ds = TensorDataset([x, y])
    m = _make_model(metrics=[Accuracy()])
    m.fit(ds, batch_size=8, epochs=1, verbose=0, shuffle=False,
          steps_per_loop=4)
    snap = obs.default_registry().snapshot()
    assert snap.get("train_loop_dispatch_seconds_count", 0) >= 1
    assert snap.get("train_loop_slab_size_count", 0) >= 1
    assert snap.get("train_loop_slabs", 0) >= 1
    # the fit() epoch-end freeze coerces → at least one drain observed
    assert snap.get("train_loop_drain_seconds_count", 0) >= 1
    # prefetch wait histogram exists (observed by the slab iterator)
    assert "train_loop_prefetch_wait_seconds_count" in snap


@pytest.mark.parametrize("origin", ["env", "checkout"])
def test_compilation_cache_placed_from_outside(tmp_path, monkeypatch,
                                               origin):
    """The compile cache's directory comes from outside the program:
    JAX_COMPILATION_CACHE_DIR where it is set (the program sets no
    other), one fixed path in the checkout where it is not."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.core import compile_cache
    cache = str(tmp_path / "xla-cache")
    if origin == "env":
        monkeypatch.setenv(compile_cache.ENV_VAR, cache)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", cache)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(compile_cache, "_enabled", None)
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        x, y = _data(16)
        m = _make_model()
        m.train_batch([x], [y])
        assert compile_cache.enable() == (cache, origin)
        assert jax.config.jax_compilation_cache_dir == cache
        assert os.listdir(cache), "no persistent cache entries written"
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
