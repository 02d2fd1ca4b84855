"""spawn + small top-level parity shims (ref: test_spawn_and_launch.py,
test_iinfo_and_finfo.py)."""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import spawn


def _worker_write(out_dir):
    import json
    rank = os.environ["PADDLE_TRAINER_ID"]
    n = os.environ["PADDLE_TRAINERS_NUM"]
    with open(os.path.join(out_dir, f"r{rank}.json"), "w") as f:
        json.dump({"rank": int(rank), "n": int(n)}, f)


def _worker_fail():
    raise ValueError("rank exploded")


def _join_bounded(ctx, seconds=50):
    """join() with a bound of its own; stragglers are killed so a hung
    rank fails this test instead of holding the suite."""
    try:
        return ctx.join(timeout=seconds)
    finally:
        for p in ctx.processes:
            if p.exitcode is None:
                p.kill()


def test_spawn_runs_ranks_with_env(tmp_path):
    assert _join_bounded(spawn(_worker_write, args=(str(tmp_path),),
                               nprocs=3, join=False))
    import json
    got = sorted(json.load(open(tmp_path / f"r{r}.json"))["rank"]
                 for r in range(3))
    assert got == [0, 1, 2]


def test_spawn_propagates_worker_error(tmp_path):
    with pytest.raises(RuntimeError, match="rank exploded"):
        _join_bounded(spawn(_worker_fail, nprocs=2, join=False))


def test_iinfo_finfo():
    assert pt.iinfo("int8").max == 127
    assert pt.iinfo("int64").min < 0
    assert float(pt.finfo("float32").max) > 1e38
    assert float(pt.finfo("bfloat16").eps) == pytest.approx(0.0078125)


def test_version_and_sysconfig():
    assert pt.version.full_version.count(".") == 2
    assert os.path.isdir(pt.sysconfig.get_include())
    assert any(f.endswith(".cc") for f in
               os.listdir(pt.sysconfig.get_include()))


def test_callbacks_namespace_and_metric_accuracy():
    import jax.numpy as jnp
    assert pt.callbacks.EarlyStopping is not None
    logits = jnp.asarray([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    labels = jnp.asarray([1, 0, 0])
    acc = float(pt.metric.accuracy(logits, labels))
    assert acc == pytest.approx(2 / 3)
    acc2 = float(pt.metric.accuracy(logits, labels, k=2))
    assert acc2 == 1.0
