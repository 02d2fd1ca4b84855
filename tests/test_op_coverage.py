"""Mechanical op-coverage gate (VERDICT r2 item 9) + targeted checks for
the round-3 coverage fills (detection ops, Exponential, pad3d).

The coverage tool (tools/op_coverage.py) enumerates the reference's
public op surface from its api yaml registry (reference:
paddle/phi/api/yaml/api.yaml + legacy_api.yaml) and resolves every name
here; the gate asserts the missing list stays empty."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
import paddle_tpu.vision.ops as vops  # noqa: E402


def test_reference_op_surface_fully_covered():
    from tools.op_coverage import classify
    r = classify()
    assert not r["missing"], r["missing"]
    covered = len(r["direct"]) + len(r["alias"])
    assert covered >= 250, covered  # VERDICT r2 target


def test_roi_pool_max_per_bin():
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 1, 4, 4)
    boxes = np.array([[0.0, 0.0, 3.0, 3.0]])
    out = vops.roi_pool(x, boxes, [1], output_size=2)
    # quantized 2x2 bins over the full 4x4 map: max of each quadrant
    np.testing.assert_allclose(
        np.asarray(out)[0, 0], [[5.0, 7.0], [13.0, 15.0]])


def test_psroi_pool_position_sensitive_average():
    # 4 channels = 1 out-channel * 2x2 bins; each bin reads its own slice
    x = jnp.stack([jnp.full((4, 4), float(c)) for c in range(4)])[None]
    boxes = np.array([[0.0, 0.0, 4.0, 4.0]])
    out = vops.psroi_pool(x, boxes, [1], output_size=2)
    np.testing.assert_allclose(
        np.asarray(out)[0, 0], [[0.0, 1.0], [2.0, 3.0]])


def test_temporal_shift_moves_channel_folds():
    n, t, c, h, w = 1, 3, 4, 1, 1
    x = jnp.arange(n * t * c, dtype=jnp.float32).reshape(n * t, c, h, w)
    out = np.asarray(vops.temporal_shift(x, seg_num=t, shift_ratio=0.25))
    xr = np.asarray(x).reshape(n, t, c)
    outr = out.reshape(n, t, c)
    # channel 0: from t-1 (zero at t=0); channel 1: from t+1 (zero at
    # t=T-1); channels 2-3 unchanged
    np.testing.assert_allclose(outr[0, :, 0], [0.0, xr[0, 0, 0],
                                               xr[0, 1, 0]])
    np.testing.assert_allclose(outr[0, :, 1], [xr[0, 1, 1], xr[0, 2, 1],
                                               0.0])
    np.testing.assert_allclose(outr[0, :, 2:], xr[0, :, 2:])


def test_yolo_box_decode_shapes_and_center():
    n, an, cls, hw = 1, 2, 3, 2
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, an * (5 + cls), hw, hw), jnp.float32)
    boxes, scores = vops.yolo_box(x, np.array([[64, 64]]), [10, 13, 16, 30],
                                  class_num=cls, conf_thresh=0.0,
                                  downsample_ratio=32)
    assert boxes.shape == (n, hw * hw * an, 4)
    assert scores.shape == (n, hw * hw * an, cls)
    b = np.asarray(boxes)
    assert (b >= 0).all() and (b <= 63).all()  # clipped to img
    # zero logits decode to the cell center: cx=(0.5+gx)/W
    x0 = jnp.zeros_like(x)
    b0, s0 = vops.yolo_box(x0, np.array([[64, 64]]), [10, 13, 16, 30],
                           class_num=cls, conf_thresh=0.9,
                           downsample_ratio=32, clip_bbox=False)
    cx = (np.asarray(b0)[0, 0, 0] + np.asarray(b0)[0, 0, 2]) / 2
    np.testing.assert_allclose(cx, 0.5 / hw * 64, rtol=1e-5)
    # conf sigmoid(0)=0.5 < 0.9 threshold → all scores zeroed
    np.testing.assert_allclose(np.asarray(s0), 0.0)


def test_exponential_distribution():
    from paddle_tpu.distribution import Exponential
    pt.seed(0)
    d = Exponential(rate=jnp.asarray([2.0]))
    np.testing.assert_allclose(np.asarray(d.mean), [0.5])
    np.testing.assert_allclose(np.asarray(d.variance), [0.25])
    s = d.sample((20000,))
    assert abs(float(s.mean()) - 0.5) < 0.02
    np.testing.assert_allclose(
        float(d.log_prob(jnp.asarray(1.0))[0]),
        float(np.log(2.0) - 2.0), rtol=1e-6)
    np.testing.assert_allclose(float(d.cdf(jnp.asarray(0.5))[0]),
                               1 - np.exp(-1.0), rtol=1e-6)


def test_pad3d_pads_innermost_first():
    x = jnp.ones((1, 1, 2, 2, 2))
    out = F.pad3d(x, [1, 1, 0, 0, 0, 0])       # pad W only
    assert out.shape == (1, 1, 2, 2, 4)
    out = F.pad3d(x, [0, 0, 0, 0, 2, 0])       # pad D before
    assert out.shape == (1, 1, 4, 2, 2)
    with pytest.raises(ValueError, match="5-D"):
        F.pad3d(jnp.ones((2, 2)), [1, 1, 1, 1, 1, 1])
