"""Serving concurrency tests (VERDICT r3 missing #6 / ask #8).

The reference serves AnalysisPredictor behind multi-threaded servers
with one predictor clone per thread (ref:
paddle/fluid/inference/api/analysis_predictor.h:95 + capi_exp thread
pools). Here ONE predictor serves all threads (PJRT execute is
re-entrant; per-request result handles remove the shared-output race),
and a DynamicBatcher coalesces queued rows into full-batch device
calls — the TPU-appropriate inversion of clone-per-thread.

Batcher mechanics run against a stub predictor (no hardware); the true
concurrent-run test follows test_inference_native's skip-on-busy
pattern against the real plugin.
"""

import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu.inference import DynamicBatcher


class StubPredictor:
    """Deterministic stand-in: y = x * 2 rowwise, records call shapes."""

    def __init__(self, delay=0.0):
        self.calls = []
        self.delay = delay
        self.lock = threading.Lock()

    def run(self, inputs):
        with self.lock:
            self.calls.append([a.shape for a in inputs])
        if self.delay:
            time.sleep(self.delay)
        return [inputs[0] * 2.0]


def test_batcher_coalesces_to_one_device_call():
    pred = StubPredictor()
    with DynamicBatcher(pred, max_batch=8, max_delay_ms=50) as b:
        futs = [b.submit([np.full((1, 4), float(i), np.float32)])
                for i in range(8)]
        outs = [f.result(timeout=10) for f in futs]
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o[0], np.full((1, 4), 2.0 * i))
        assert o[0].shape == (1, 4)
    # 8 single-row requests, batch capacity 8 -> ideally 1 call; the
    # worker may cut an early pack before all requests enqueue, but
    # coalescing must beat request-per-call
    assert pred.calls and all(s[0] == (8, 4) for s in pred.calls)
    assert b.n_device_calls < 8
    assert b.n_requests == 8


def test_batcher_pads_partial_batch():
    pred = StubPredictor()
    with DynamicBatcher(pred, max_batch=8, max_delay_ms=5) as b:
        out = b.run([np.ones((3, 2), np.float32)])
    assert out[0].shape == (3, 2)  # padding sliced back off
    assert pred.calls[0][0] == (8, 2)  # device saw the full batch


def test_batcher_multirow_and_overflow_holdover():
    """5+5 rows into batch 8: second request must be deferred to a
    second pack, order preserved, both correct."""
    pred = StubPredictor(delay=0.01)
    with DynamicBatcher(pred, max_batch=8, max_delay_ms=30) as b:
        f1 = b.submit([np.full((5, 2), 1.0, np.float32)])
        f2 = b.submit([np.full((5, 2), 3.0, np.float32)])
        o1 = f1.result(timeout=10)[0]
        o2 = f2.result(timeout=10)[0]
    np.testing.assert_allclose(o1, np.full((5, 2), 2.0))
    np.testing.assert_allclose(o2, np.full((5, 2), 6.0))
    assert b.n_device_calls == 2


def test_batcher_rejects_oversized_and_ragged():
    pred = StubPredictor()
    with DynamicBatcher(pred, max_batch=4, max_delay_ms=1) as b:
        with pytest.raises(ValueError):
            b.submit([np.ones((5, 2), np.float32)])
        with pytest.raises(ValueError):
            b.submit([np.ones((2, 2), np.float32),
                      np.ones((3, 2), np.float32)])


def test_batcher_propagates_run_errors():
    class Boom:
        def run(self, inputs):
            raise RuntimeError("device gone")

    with DynamicBatcher(Boom(), max_batch=4, max_delay_ms=1) as b:
        fut = b.submit([np.ones((1, 2), np.float32)])
        with pytest.raises(RuntimeError, match="device gone"):
            fut.result(timeout=10)


def test_batcher_survives_mismatched_trailing_shapes():
    """A pack whose rows can't concatenate must fail ITS futures and
    leave the worker alive for later requests."""
    pred = StubPredictor(delay=0.01)
    with DynamicBatcher(pred, max_batch=8, max_delay_ms=30) as b:
        f1 = b.submit([np.ones((1, 4), np.float32)])
        f2 = b.submit([np.ones((1, 6), np.float32)])  # ragged trailing
        excs = 0
        for f in (f1, f2):
            try:
                f.result(timeout=10)
            except ValueError:
                excs += 1
        assert excs >= 1  # at least the pack that mixed shapes failed
        out = b.run([np.ones((1, 4), np.float32)])  # worker still alive
        np.testing.assert_allclose(out[0], np.full((1, 4), 2.0))


def test_batcher_close_contract():
    """close() completes accepted work, then rejects new submits —
    FIFO ordering (submit's check+put and close's set+STOP share one
    lock) means every accepted request is ahead of STOP and served."""
    pred = StubPredictor(delay=0.01)
    b = DynamicBatcher(pred, max_batch=4, max_delay_ms=1)
    futs = [b.submit([np.full((1, 2), float(i), np.float32)])
            for i in range(6)]
    b.close()
    assert not b._worker.is_alive()
    for i, f in enumerate(futs):  # all accepted requests completed
        np.testing.assert_allclose(f.result(timeout=5)[0],
                                   np.full((1, 2), 2.0 * i))
    with pytest.raises(RuntimeError, match="batcher closed"):
        b.submit([np.ones((1, 2), np.float32)])


def test_batcher_drain_serves_accepted_work():
    """A graceful close must FLUSH work whose submit() already
    succeeded (r4 advisor finding), not fail it: queued and held items
    are packed like the live loop and every future resolves."""
    from concurrent.futures import Future
    pred = StubPredictor()
    b = DynamicBatcher(pred, max_batch=4, max_delay_ms=1)
    b.close()
    f1, f2 = Future(), Future()
    b._q.put(([np.ones((1, 2), np.float32)], 1, f1))
    b._held = ([np.full((1, 2), 3.0, np.float32)], 1, f2)
    b._drain()
    np.testing.assert_allclose(f1.result(timeout=5)[0],
                               np.full((1, 2), 2.0))
    np.testing.assert_allclose(f2.result(timeout=5)[0],
                               np.full((1, 2), 6.0))
    assert b._held is None
    # both fit one pack: the drain coalesces like the live loop
    assert pred.calls and pred.calls[-1][0][0] == 4  # padded to max


def test_batcher_close_resolves_inflight_submits():
    """End-to-end: submits accepted just before close() all resolve
    with results after close() returns."""
    pred = StubPredictor()
    b = DynamicBatcher(pred, max_batch=8, max_delay_ms=50)
    futs = [b.submit([np.full((1, 2), float(i), np.float32)])
            for i in range(5)]
    b.close()
    for i, f in enumerate(futs):
        np.testing.assert_allclose(f.result(timeout=5)[0],
                                   np.full((1, 2), 2.0 * i))
    with pytest.raises(RuntimeError, match="batcher closed"):
        b.submit([np.zeros((1, 2), np.float32)])


def test_batcher_threaded_clients_all_served():
    pred = StubPredictor(delay=0.002)
    results = {}
    with DynamicBatcher(pred, max_batch=4, max_delay_ms=10) as b:
        def client(i):
            out = b.run([np.full((1, 3), float(i), np.float32)])
            results[i] = out[0]

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
    assert len(results) == 16
    for i, o in results.items():
        np.testing.assert_allclose(o, np.full((1, 3), 2.0 * i))
    assert b.n_device_calls < 16  # coalescing actually happened


# ---- serve_llm error-mapping contract over real HTTP (ISSUE 6)
#
# The fleet router routes on these exact status codes; pinning them
# here keeps the engine front and the HTTPReplica client in lockstep:
# shed/queue-full → 429, draining → 503, deadline → 504, cancel → 499.


import json as _json
from urllib.error import HTTPError
from urllib.request import Request, urlopen


@pytest.fixture(scope="module")
def llm_http():
    """One tiny engine behind serve_llm, shared by the mapping tests
    (each test restores any engine state it pokes)."""
    from paddle_tpu.inference.llm import serve_llm
    from paddle_tpu.serving.replica import make_engine_from_spec
    eng = make_engine_from_spec({"vocab": 97, "layers": 2,
                                 "hidden": 64})
    eng.submit([1, 2, 3], max_new_tokens=2).result(timeout=300)  # warm
    srv = serve_llm(eng)
    host, port = srv.server_address[:2]
    yield eng, f"http://{host}:{port}"
    srv.shutdown()
    eng.close()


def _post(base, path, body):
    req = Request(base + path, data=_json.dumps(body).encode(),
                  headers={"Content-Type": "application/json"})
    try:
        with urlopen(req, timeout=120) as r:
            return r.status, _json.loads(r.read())
    except HTTPError as e:
        return e.code, _json.loads(e.read())


def test_serve_llm_ok_carries_request_id(llm_http):
    _, base = llm_http
    code, out = _post(base, "/generate",
                      {"prompt_ids": [4, 5, 6], "max_new_tokens": 3})
    assert code == 200
    assert len(out["output_ids"]) == 3
    assert isinstance(out["request_id"], int)


def test_serve_llm_shed_maps_to_429(llm_http):
    eng, base = llm_http
    saved = eng.max_pending
    eng.max_pending = 0          # every submission is queue overflow
    try:
        code, out = _post(base, "/generate", {"prompt_ids": [1, 2]})
    finally:
        eng.max_pending = saved
    assert code == 429, (code, out)
    assert out["outcome"] == "shed" and out["reason"] == "queue_full"


def test_serve_llm_draining_maps_to_503(llm_http):
    eng, base = llm_http
    eng._health = "draining"     # the sticky latch, forced
    try:
        code, out = _post(base, "/generate", {"prompt_ids": [1, 2]})
    finally:
        eng.reset_health()
    assert code == 503, (code, out)
    assert out["outcome"] == "shed" and out["reason"] == "draining"
    assert eng.health == "healthy"


def test_serve_llm_deadline_maps_to_504(llm_http):
    _, base = llm_http
    code, out = _post(base, "/generate",
                      {"prompt_ids": [1, 2, 3], "deadline_s": -1.0})
    assert code == 504, (code, out)
    assert out["outcome"] == "deadline"


def test_serve_llm_cancel_maps_to_499(llm_http):
    eng, base = llm_http
    res = {}

    def client():
        res["resp"] = _post(base, "/generate",
                            {"prompt_ids": [7, 8, 9, 10],
                             "max_new_tokens": 80})

    t = threading.Thread(target=client)
    t.start()
    deadline = time.time() + 60
    rid = None
    while time.time() < deadline and rid is None:
        ids = list(eng._by_id)
        rid = ids[0] if ids else None
        time.sleep(0.005)
    assert rid is not None, "request never reached the engine"
    code, out = _post(base, "/cancel", {"request_id": rid})
    assert code == 200 and out["cancelled"] is True
    t.join(timeout=120)
    code, out = res["resp"]
    assert code == 499, (code, out)
    assert out["outcome"] == "cancelled"
    # cancelling a resolved request reports False, not an error
    code, out = _post(base, "/cancel", {"request_id": rid})
    assert code == 200 and out["cancelled"] is False


def test_serve_llm_nonce_passthrough_pins_stream(llm_http):
    _, base = llm_http
    body = {"prompt_ids": [11, 12, 13, 14], "max_new_tokens": 5,
            "temperature": 0.9, "nonce": 4242}
    _, out1 = _post(base, "/generate", body)
    _, out2 = _post(base, "/generate", body)
    assert out1["output_ids"] == out2["output_ids"]


def test_serve_llm_bad_request_maps_to_400(llm_http):
    _, base = llm_http
    code, out = _post(base, "/generate", {"prompt_ids": []})
    assert code == 400 and "error" in out


def test_serve_llm_response_carries_stream_integrity_headers(llm_http):
    """ISSUE 19 contract: a generate response carries its chain head
    (X-Stream-Digest) and the serving engine's knob fingerprint
    (X-Engine-Knobs) as headers, matching the body, so a caller can
    verify the stream without parsing JSON."""
    from paddle_tpu.observability import audit
    _, base = llm_http
    req = Request(base + "/generate",
                  data=_json.dumps({"prompt_ids": [7, 8, 9],
                                    "max_new_tokens": 4,
                                    "nonce": 99}).encode(),
                  headers={"Content-Type": "application/json"})
    with urlopen(req, timeout=120) as r:
        code, hdrs, out = r.status, dict(r.headers), \
            _json.loads(r.read())
    assert code == 200 and out["nonce"] == 99
    # header == body == the chain recomputed from the tokens
    assert hdrs["X-Stream-Digest"] == out["stream_digest"] == \
        audit.chain_of(99, out["output_ids"]).hex()
    knobs = _json.loads(hdrs["X-Engine-Knobs"])
    assert knobs == out["knobs"]
    assert set(knobs) == {"kv_dtype", "spec_k", "draft"}


# ---- real-plugin concurrency (skip-on-busy, like test_inference_native)


def _plugin_available() -> bool:
    try:
        from paddle_tpu import inference
        inference.default_plugin()
        return True
    except Exception:
        return False


@pytest.mark.slow
@pytest.mark.skipif(not _plugin_available(),
                    reason="no PJRT plugin .so on this machine")
def test_concurrent_predictor_run_matches_serial(tmp_path):
    import paddle_tpu as pt
    from paddle_tpu import jit

    class MLP(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = pt.nn.Linear(16, 64)
            self.l2 = pt.nn.Linear(64, 8)

        def forward(self, x):
            return self.l2(pt.nn.functional.relu(self.l1(x)))

    pt.seed(0)
    net = MLP()
    net.eval()
    rng = np.random.RandomState(0)
    xs = [rng.randn(4, 16).astype(np.float32) for _ in range(12)]
    refs = [np.asarray(net(x)) for x in xs]
    path = str(tmp_path / "artifact")
    jit.save(net, path, input_spec=[jit.InputSpec([4, 16], "float32")])

    from paddle_tpu import inference
    os.environ.setdefault("PT_PJRT_CREATE_TIMEOUT", "90")
    try:
        pred = inference.create_predictor(inference.Config(path))
    except TimeoutError as e:
        pytest.skip(f"device unavailable for native predictor: {e}")

    outs = [None] * len(xs)
    errs = []

    def worker(tid):
        try:
            for i in range(tid, len(xs), 4):
                outs[i] = pred.run([xs[i]])[0]
        except BaseException as e:  # surfaced below
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs, errs
    for o, r in zip(outs, refs):
        assert o is not None
        np.testing.assert_allclose(o, r, atol=5e-2, rtol=2e-2)


@pytest.mark.slow
@pytest.mark.skipif(not _plugin_available(),
                    reason="no PJRT plugin .so on this machine")
def test_standalone_cpp_server_binary(tmp_path):
    """predictor_main.cc → ptserve: a pure-C++ process (zero Python)
    loads the artifact, serves concurrent requests through the
    thread-safe API, and its output-0 checksum matches the Python
    forward (the reference's demo_ci C++ consumer proof)."""
    import json
    import subprocess
    import sys

    import paddle_tpu as pt
    from paddle_tpu import inference, jit

    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "native")
    inference._load_lib()  # ensure libptpredictor.so is current
    exe = os.path.join(native, "ptserve")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", "predictor_main.cc", "-o", exe,
         "-L.", "-lptpredictor", "-Wl,-rpath,$ORIGIN"],
        cwd=native, check=True, capture_output=True)

    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(16, 32), pt.nn.Tanh(),
                           pt.nn.Linear(32, 4))
    net.eval()
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    ref_sum = float(np.asarray(net(x)).astype(np.float64).sum())
    art = str(tmp_path / "artifact")
    jit.save(net, art, input_spec=[jit.InputSpec([8, 16], "float32")])
    np.save(tmp_path / "x.npy", x)

    try:
        proc = subprocess.run(
            [exe, inference.default_plugin(),
             inference.default_plugin_options(), art,
             str(tmp_path / "x.npy"), "--threads", "3", "--iters", "4"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "PT_PJRT_CREATE_TIMEOUT": "120"})
    except subprocess.TimeoutExpired:
        pytest.skip("device unavailable (serve binary timed out)")
    if proc.returncode == 3 or (proc.returncode != 0 and (
            "holds the device" in proc.stderr
            or "Unavailable" in proc.stderr
            or "UNAVAILABLE" in proc.stderr)):
        pytest.skip(f"device unavailable: {proc.stderr[-200:]}")
    assert proc.returncode == 0, proc.stderr[-800:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["requests"] == 12
    np.testing.assert_allclose(out["out0_sum"], ref_sum,
                               rtol=2e-2, atol=1e-2)


def test_serve_binary_npy_parser():
    """Hardware-free: ptserve --parse-only must read multi-dim npy
    headers exactly (a comma-split once truncated (8,16) to (8,))."""
    import json
    import subprocess
    import tempfile

    from paddle_tpu import inference

    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "native")
    inference._build_so()  # libptpredictor.so is built at first use
    exe = os.path.join(native, "ptserve")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", "predictor_main.cc", "-o", exe,
         "-L.", "-lptpredictor", "-Wl,-rpath,$ORIGIN"],
        cwd=native, check=True, capture_output=True)
    with tempfile.TemporaryDirectory() as td:
        cases = {
            "a": np.ones((8, 16), np.float32),
            "b": np.arange(6, dtype=np.int64),
            "c": np.zeros((2, 3, 4), np.float64),
            "d": np.zeros((5,), np.int32),
        }
        paths = []
        for name, arr in cases.items():
            p = os.path.join(td, f"{name}.npy")
            np.save(p, arr)
            paths.append((p, arr))
        proc = subprocess.run(
            [exe, "x", "", "y"] + [p for p, _ in paths]
            + ["--parse-only"], capture_output=True, text=True,
            timeout=60)
        assert proc.returncode == 0, proc.stderr
        lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
        for (p, arr), rec in zip(paths, lines):
            assert rec["dims"] == list(arr.shape), (p, rec)
            assert rec["nbytes"] == arr.nbytes, (p, rec)
