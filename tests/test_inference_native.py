"""Native (C++ PJRT) serving tests — the AnalysisPredictor analog
(ref: paddle/fluid/inference/api/analysis_predictor.h:95; tests model
the reference's inference api_impl_tester pattern: save from Python,
load+run natively, compare outputs).

The predictor is exercised both in-process (ctypes) and in a FRESH
subprocess with no prior jax state — the serving deployment shape.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import jit


def _plugin_available() -> bool:
    try:
        from paddle_tpu import inference
        inference.default_plugin()
        return True
    except Exception:
        return False


pytestmark = [
    pytest.mark.skipif(not _plugin_available(),
                       reason="no PJRT plugin .so on this machine"),
    pytest.mark.slow,  # smoke tier skips (tools/ci.sh --smoke)
]


def _save_and_serve(net, x, tmp_path, atol):
    net.eval()
    ref = np.asarray(net(x))
    path = str(tmp_path / "artifact")
    jit.save(net, path,
             input_spec=[jit.InputSpec(list(x.shape), str(x.dtype))])
    from paddle_tpu import inference
    os.environ.setdefault("PT_PJRT_CREATE_TIMEOUT", "90")
    try:
        pred = inference.create_predictor(inference.Config(path))
    except TimeoutError as e:
        pytest.skip(f"device unavailable for native predictor: {e}")
    out = pred.run([x])[0]
    assert out.shape == ref.shape
    # CPU-exported f32 convs run through the MXU's bf16 passes on TPU:
    # ~1% relative deviation is expected, not a serving bug
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=2e-2)
    return path, ref


def test_native_predictor_lenet(tmp_path):
    from paddle_tpu.models.lenet import LeNet
    pt.seed(0)
    x = np.random.RandomState(0).randn(2, 1, 28, 28).astype(np.float32)
    _save_and_serve(LeNet(), x, tmp_path, atol=5e-2)


def test_native_predictor_resnet(tmp_path):
    from paddle_tpu.models.resnet import resnet18
    pt.seed(0)
    x = np.random.RandomState(0).randn(1, 3, 64, 64).astype(np.float32)
    _save_and_serve(resnet18(num_classes=10), x, tmp_path, atol=1e-1)


def test_native_predictor_gpt(tmp_path):
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTConfig
    pt.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=16,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_flash=False)
    net = GPTForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 128, (2, 16)).astype(
        np.int64)
    _save_and_serve(net, ids, tmp_path, atol=5e-2)


def test_native_predictor_fresh_process(tmp_path):
    """Serving shape: artifact produced here, consumed by a brand-new
    process that never touches this process's jax state."""
    from paddle_tpu.models.lenet import LeNet
    pt.seed(0)
    net = LeNet()
    net.eval()
    x = np.random.RandomState(1).randn(2, 1, 28, 28).astype(np.float32)
    ref = np.asarray(net(x))
    path = str(tmp_path / "artifact")
    jit.save(net, path, input_spec=[jit.InputSpec([2, 1, 28, 28],
                                                  "float32")])
    np.save(tmp_path / "x.npy", x)

    script = textwrap.dedent(f"""
        import numpy as np
        from paddle_tpu import inference
        x = np.load({str(tmp_path / 'x.npy')!r})
        pred = inference.create_predictor(
            inference.Config({path!r}))
        out = pred.run([x])[0]
        np.save({str(tmp_path / 'out.npy')!r}, out)
        print("SERVED_OK")
    """)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # let the server pick its backend
    env.setdefault("PT_PJRT_CREATE_TIMEOUT", "90")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=os.path.dirname(
                              os.path.dirname(os.path.abspath(__file__))))
    if "TimeoutError" in proc.stderr and "holds the device" in proc.stderr:
        pytest.skip("device unavailable for native predictor")
    assert "SERVED_OK" in proc.stdout, proc.stderr[-2000:]
    out = np.load(tmp_path / "out.npy")
    np.testing.assert_allclose(out, ref, atol=5e-2)


def test_artifact_has_native_files(tmp_path):
    from paddle_tpu.models.lenet import LeNet
    pt.seed(0)
    path = str(tmp_path / "a")
    jit.save(LeNet(), path,
             input_spec=[jit.InputSpec([1, 1, 28, 28], "float32")])
    for f in ("program.stablehlo", "program.mlir.bc", "params.pbin",
              "meta.json"):
        assert os.path.exists(os.path.join(path, f)), f
    meta = json.load(open(os.path.join(path, "meta.json")))
    assert meta["n_state_args"] > 0
    assert meta["outputs"][0]["shape"] == [1, 10]
