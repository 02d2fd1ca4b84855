"""Regression tests for review findings (round-1 code review)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_pad_innermost_first():
    x = jnp.zeros((1, 1, 3, 3))
    y = F.pad(x, [1, 0, 0, 0])  # pad left of W only
    assert y.shape == (1, 1, 3, 4)
    y2 = F.pad(x, [0, 0, 2, 0])  # pad top of H only
    assert y2.shape == (1, 1, 5, 3)


def test_pad_matches_torch():
    torch = pytest.importorskip("torch")
    x = np.random.randn(2, 3, 4, 5).astype(np.float32)
    pad = [1, 2, 3, 4]
    got = np.asarray(F.pad(jnp.asarray(x), pad, value=7.0))
    ref = torch.nn.functional.pad(torch.tensor(x), pad, value=7.0).numpy()
    np.testing.assert_array_equal(got, ref)


def test_frozen_param_not_updated():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.frozen = nn.Parameter(jnp.ones((4,)), trainable=False)
            self.lin = nn.Linear(4, 1)

        def forward(self, x):
            return self.lin(x * self.frozen)

    from paddle_tpu.io import TensorDataset
    from paddle_tpu.optimizer import SGD
    net = Net()
    model = pt.Model(net)
    model.prepare(optimizer=SGD(learning_rate=0.1, parameters=net),
                  loss=nn.MSELoss())
    x = np.random.randn(8, 4).astype(np.float32)
    y = np.random.randn(8, 1).astype(np.float32)
    model.fit(TensorDataset([x, y]), batch_size=8, epochs=2, verbose=0)
    np.testing.assert_array_equal(np.asarray(net.frozen), 1.0)
    # but the trainable linear moved
    assert model._step_count == 2


def test_adamw_decay_exclusion():
    from paddle_tpu.optimizer import AdamW
    params = {"w": jnp.ones((4,)), "norm.bias": jnp.ones((4,))}
    opt = AdamW(learning_rate=0.0, weight_decay=0.5,
                apply_decay_param_fun=lambda n: "norm" not in n)
    # lr=0 isolates... decay is multiplied by lr, so use lr>0 and zero grads
    opt = AdamW(learning_rate=0.1, weight_decay=0.5,
                apply_decay_param_fun=lambda n: "norm" not in n)
    state = opt.init_state(params)
    zero_g = {k: jnp.zeros_like(v) for k, v in params.items()}
    p1, _ = opt.apply_gradients(params, zero_g, state, 0)
    assert float(p1["w"][0]) < 1.0            # decayed
    np.testing.assert_allclose(np.asarray(p1["norm.bias"]), 1.0)  # excluded


def test_transformer_clone_keeps_activation():
    proto = nn.TransformerEncoderLayer(16, 2, 32, 0.1, activation="gelu",
                                       normalize_before=True)
    enc = nn.TransformerEncoder(proto, 3)
    for layer in enc.layers:
        assert layer.activation is F.gelu
        assert layer.normalize_before


def test_interpolate_align_corners_matches_torch():
    torch = pytest.importorskip("torch")
    x = np.random.randn(1, 2, 5, 7).astype(np.float32)
    got = np.asarray(F.interpolate(jnp.asarray(x), size=(10, 3),
                                   mode="bilinear", align_corners=True))
    ref = torch.nn.functional.interpolate(
        torch.tensor(x), size=(10, 3), mode="bilinear",
        align_corners=True).numpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_nonpersistable_buffer_roundtrip():
    class L(nn.Layer):
        def __init__(self):
            super().__init__()
            self.register_buffer("tmp", jnp.zeros((2,)), persistable=False)
            self.register_buffer("keep", jnp.ones((2,)))

        def forward(self, x):
            return x

    l1 = L()
    sd = l1.state_dict()
    assert "tmp" not in sd and "keep" in sd
    L().set_state_dict(sd)  # must not raise


def test_fan_in_out_conv_layout():
    from paddle_tpu.nn.initializer import _fan_in_out
    fi, fo = _fan_in_out([64, 32, 3, 3])  # [out, in, kh, kw]
    assert fi == 32 * 9
    assert fo == 64 * 9


def test_named_rng_streams_stable():
    import subprocess, sys
    # pin the fresh interpreters to CPU: this tests RNG determinism,
    # and a child of a CPU test never takes a device
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import paddle_tpu as pt; import numpy as np; pt.seed(3); "
            "from paddle_tpu.core import rng; "
            "print(np.asarray(jax.random.key_data("
            "rng.next_key('init'))).tolist())")
    outs = set()
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=45)
        assert proc.returncode == 0, proc.stderr[-1000:]
        outs.add(proc.stdout.strip())
    assert len(outs) == 1  # identical across fresh interpreters


# -- round-2 advisor fixes -------------------------------------------------

def test_pylayer_nested_attrs_not_swapped():
    """Two applies of the same PyLayer with different ctx.attrs inside one
    differentiated function must keep their own attrs in backward
    (round-1 advisor: FIFO side-stack swapped them under custom_vjp's
    LIFO backward order; attrs now ride the residuals)."""
    from paddle_tpu import autograd

    class Scale(autograd.PyLayer):
        @staticmethod
        def forward(ctx, x, s):
            ctx.attrs["s"] = s
            return x * s

        @staticmethod
        def backward(ctx, g):
            return g * ctx.attrs["s"], jnp.zeros(())

    def f(x):
        y = Scale.apply(x, 3.0)   # dy/dx = 3
        z = Scale.apply(y, 4.0)   # dz/dy = 4
        return z

    g = jax.grad(f)(jnp.asarray(2.0))
    assert float(g) == 12.0  # was 11 with swapped attrs

    # also correct under jit (retracing-safe: no side stack)
    gj = jax.jit(jax.grad(f))(jnp.asarray(2.0))
    assert float(gj) == 12.0


def test_vjp_multi_output_default_cotangent():
    from paddle_tpu import autograd

    def f(x):
        return (x * 2.0, x * 3.0)

    out, g = autograd.vjp(f, jnp.asarray(1.0))
    assert float(g) == 5.0


def test_totensor_scales_by_dtype_not_data():
    from paddle_tpu.vision.transforms import ToTensor
    dark = np.zeros((4, 4, 3), np.uint8)
    dark[0, 0, 0] = 1  # max == 1: the old data-based check skipped /255
    out = ToTensor()(dark)
    assert abs(float(out.max()) - 1.0 / 255.0) < 1e-7
    # float input in [0,1] is untouched
    f = np.full((4, 4, 3), 0.5, np.float32)
    assert float(ToTensor()(f).max()) == 0.5


def test_viterbi_include_bos_eos_tag():
    """Against a brute force with the reference convention: start tag =
    last transitions row, stop tag = second-to-last row
    (viterbi_decode_kernel.cc:222-252)."""
    from paddle_tpu.text import viterbi_decode
    import itertools
    rs = np.random.RandomState(3)
    b, s, n = 2, 4, 4
    pot = rs.randn(b, s, n).astype(np.float32)
    trans = rs.randn(n, n).astype(np.float32)
    lengths = np.array([4, 2], np.int32)
    scores, paths = viterbi_decode(pot, trans, lengths,
                                   include_bos_eos_tag=True)
    for bi in range(b):
        L = int(lengths[bi])
        best, bestp = -1e30, None
        for tags in itertools.product(range(n), repeat=L):
            sc = trans[n - 1, tags[0]] + pot[bi, 0, tags[0]]
            for t in range(1, L):
                sc += trans[tags[t - 1], tags[t]] + pot[bi, t, tags[t]]
            sc += trans[n - 2, tags[L - 1]]
            if sc > best:
                best, bestp = sc, tags
        assert abs(float(scores[bi]) - best) < 1e-4
        assert list(np.asarray(paths[bi])[:L]) == list(bestp)
