"""Reliability layer (docs/RELIABILITY.md): deterministic fault
injection, the shared retry/deadline policy, and the hardened engine
failure semantics — deadlines, shed, cancel, admission timeout,
device-error retry budgets, and the health state machine."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.reliability import faults
from paddle_tpu.reliability.faults import FaultInjected
from paddle_tpu.reliability.retry import (Deadline, DeadlineExceeded,
                                          RetryExhausted, RetryPolicy,
                                          as_deadline, backoff_delay)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- deadlines ----------------------------------------------------------


def test_deadline_math_and_composition():
    dl = Deadline.after(10.0)
    assert 9.0 < dl.remaining() <= 10.0
    assert not dl.expired
    tight = dl.min(Deadline.after(0.5))
    assert tight.remaining() <= 0.5
    assert dl.min(None) is dl
    assert dl.clamp(1.0) == 1.0            # per-attempt cap holds
    assert tight.clamp(5.0) <= 0.5         # deadline wins
    past = Deadline.after(-1.0)
    assert past.expired and past.clamp(3.0) == 0.0
    with pytest.raises(DeadlineExceeded):
        past.raise_if_expired("unit test")
    assert Deadline.never().remaining() == float("inf")


def test_as_deadline_coercions():
    assert as_deadline(None) is None
    dl = Deadline.after(1.0)
    assert as_deadline(dl) is dl
    assert isinstance(as_deadline(2.5), Deadline)
    assert as_deadline(2.5).remaining() <= 2.5


# -- backoff curve ------------------------------------------------------


def test_backoff_delay_growth_cap_and_jitter():
    ds = [backoff_delay(i, 0.5, cap=4.0) for i in range(6)]
    assert ds == [0.5, 1.0, 2.0, 4.0, 4.0, 4.0]   # doubles, then caps
    import random
    rng = random.Random(7)
    jittered = [backoff_delay(1, 1.0, jitter=0.5, rng=rng)
                for _ in range(50)]
    assert all(1.0 <= d <= 3.0 for d in jittered)  # 2.0 ± 50%
    assert len(set(jittered)) > 1
    # seeded → reproducible
    a = [backoff_delay(i, 1.0, jitter=0.5, rng=random.Random(3))
         for i in range(4)]
    b = [backoff_delay(i, 1.0, jitter=0.5, rng=random.Random(3))
         for i in range(4)]
    assert a == b


# -- retry policy -------------------------------------------------------


def test_retry_policy_recovers_then_exhausts():
    pol = RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0,
                      retry_on=(OSError,), scope="test")
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky) == "ok"
    assert calls["n"] == 3

    def hopeless():
        raise OSError("down")

    with pytest.raises(RetryExhausted) as ei:
        pol.call(hopeless, describe="hopeless op")
    assert isinstance(ei.value.last, OSError)
    assert ei.value.__cause__ is ei.value.last
    assert ei.value.attempts == 3


def test_retry_policy_non_retryable_propagates_immediately():
    pol = RetryPolicy(max_attempts=5, base_delay=0.001,
                      retry_on=(OSError,))
    calls = {"n": 0}

    def wrong():
        calls["n"] += 1
        raise ValueError("protocol error, not a flaky socket")

    with pytest.raises(ValueError):
        pol.call(wrong)
    assert calls["n"] == 1


def test_retry_policy_raises_instead_of_sleeping_out_the_deadline():
    """A backoff longer than the remaining budget surfaces the
    verdict immediately — no sleep nobody is waiting for (review
    finding)."""
    pol = RetryPolicy(max_attempts=5, base_delay=10.0, jitter=0.0,
                      retry_on=(OSError,))

    def failing():
        raise OSError("down")

    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        pol.call(failing, deadline=2.0)
    assert time.monotonic() - t0 < 1.0    # did NOT sleep ~2s


def test_retry_policy_deadline_stops_the_loop():
    pol = RetryPolicy(max_attempts=50, base_delay=0.05, jitter=0.0,
                      retry_on=(OSError,))

    def failing():
        raise OSError("down")

    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        pol.call(failing, deadline=0.12)
    assert time.monotonic() - t0 < 2.0     # nowhere near 50 attempts
    with pytest.raises(DeadlineExceeded):
        pol.call(failing, deadline=Deadline.after(-1.0))


# -- fault injection ----------------------------------------------------


def test_faults_disabled_is_noop():
    # not enabled: no counting, no raising, even with a rule armed
    faults.inject("device.dispatch", nth=(1,))
    for _ in range(3):
        faults.check("device.dispatch")
    assert faults.call_count("device.dispatch") == 0
    assert faults.injected_log() == []


def test_faults_nth_rule_and_times_budget():
    faults.enable(seed=0)
    faults.inject("store.socket", nth=(2, 4), times=1)
    hits = []
    for i in range(1, 6):
        try:
            faults.check("store.socket")
        except FaultInjected as e:
            hits.append((i, e.call_index))
    assert hits == [(2, 2)]                # times=1 caps the nth pair
    assert faults.call_count("store.socket") == 5
    assert faults.injected_log() == [("store.socket", 2)]


def test_faults_probability_schedule_is_deterministic():
    faults.enable(seed=42)
    faults.inject("io.worker", p=0.3)
    want = faults.preview("io.worker", 50)
    assert want == faults.preview("io.worker", 50)   # pure
    assert 2 <= len(want) <= 30                      # sane density
    got = []
    for i in range(1, 51):
        try:
            faults.check("io.worker")
        except FaultInjected:
            got.append(i)
    assert got == want                               # live == schedule
    # a different seed moves the schedule
    assert faults.preview("io.worker", 50, seed=43) != want
    # re-enabling with the same seed replays it exactly
    faults.enable(seed=42)
    got2 = []
    for i in range(1, 51):
        try:
            faults.check("io.worker")
        except FaultInjected:
            got2.append(i)
    assert got2 == got


def test_faults_reenable_replays_times_budgets():
    """enable() must reset rule budgets: re-arming with the same
    registered rules replays the schedule (review finding)."""
    faults.inject("store.socket", nth=(1,), times=1)
    for _ in range(2):
        faults.enable(seed=7)
        with pytest.raises(FaultInjected):
            faults.check("store.socket")
        faults.check("store.socket")       # budget spent this run
        assert faults.injected_log() == [("store.socket", 1)]


def test_faults_custom_exception_factory():
    faults.enable(seed=0)
    faults.inject("store.socket", nth=(1,),
                  exc=lambda: ConnectionResetError("injected"))
    with pytest.raises(ConnectionResetError):
        faults.check("store.socket")


def test_faults_exc_factory_may_read_faults_state():
    """The factory runs OUTSIDE the module lock, so reading faults
    state from it must not deadlock (review finding)."""
    faults.enable(seed=0)
    faults.inject(
        "ckpt.write", nth=(1,),
        exc=lambda: RuntimeError(
            f"call {faults.call_count('ckpt.write')}"))
    import threading
    err = {}

    def run():
        try:
            faults.check("ckpt.write")
        except RuntimeError as e:
            err["e"] = str(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "exc factory deadlocked on the faults lock"
    assert err["e"] == "call 1"


# -- DataLoader io.worker site ------------------------------------------


def test_dataloader_io_worker_fault_reaches_consumer():
    from paddle_tpu.io import DataLoader, TensorDataset
    x = np.arange(64, dtype=np.float32).reshape(16, 4)
    ds = TensorDataset([x])
    faults.enable(seed=0)
    faults.inject("io.worker", nth=(2,))
    loader = DataLoader(ds, batch_size=4, to_device=False)
    got = []
    with pytest.raises(FaultInjected, match="io.worker"):
        for (b,) in loader:
            got.append(b)
    assert len(got) == 1                   # died on the second batch
    faults.disable()
    assert sum(1 for _ in DataLoader(ds, batch_size=4,
                                     to_device=False)) == 4


# -- checkpoint ckpt.write retry ----------------------------------------


def test_checkpoint_save_retries_injected_write_fault(tmp_path):
    from paddle_tpu.io.checkpoint import CheckpointManager
    faults.enable(seed=0)
    faults.inject("ckpt.write", nth=(1,), times=1)
    with CheckpointManager(str(tmp_path / "ck"),
                           async_save=False) as mgr:
        assert mgr.save(0, {"w": np.arange(8)})
        assert mgr.latest_step() == 0
        np.testing.assert_array_equal(mgr.restore(0)["w"], np.arange(8))
    assert ("ckpt.write", 1) in faults.injected_log()


# -- tcp store on the shared policy -------------------------------------


def test_tcp_store_client_kwarg_aliases_and_unreachable():
    from paddle_tpu.distributed.tcp_store import (StoreUnavailable,
                                                  TCPStoreClient)
    c = TCPStoreClient("127.0.0.1:1", timeout=0.2, retries=2,
                       retry_delay=0.01)
    assert c.policy.max_attempts == 2
    assert c.policy.base_delay == 0.01
    t0 = time.monotonic()
    with pytest.raises(StoreUnavailable, match="unreachable"):
        c.request({"op": "get", "k": "x"})
    assert time.monotonic() - t0 < 5.0


@pytest.mark.parametrize("exc", [None, lambda: ConnectionResetError(
    "injected")], ids=["default-FaultInjected", "ConnectionResetError"])
def test_tcp_store_request_rides_out_injected_socket_faults(exc):
    """Both the default FaultInjected AND an OSError-shaped injection
    take the same retry path (review finding: the default used to
    escape the policy untyped)."""
    from paddle_tpu.distributed.tcp_store import (TCPStoreClient,
                                                  TCPStoreServer)
    srv = TCPStoreServer(port=0)
    try:
        faults.enable(seed=0)
        faults.inject("store.socket", nth=(1,), exc=exc)
        c = TCPStoreClient(f"127.0.0.1:{srv.port}", retries=3,
                           retry_delay=0.01)
        c.request({"op": "set", "k": "a", "v": "1"})
        assert c.request({"op": "get", "k": "a"})["v"] == "1"
        assert ("store.socket", 1) in faults.injected_log()
    finally:
        faults.reset()
        srv.close()


# -- elastic restart backoff --------------------------------------------


def test_elastic_backoff_skips_graceful_preemptions():
    """A checkpointed preemption exit is healthy: it respawns with no
    delay and resets the crash-backoff curve (review finding)."""
    from paddle_tpu.distributed.elastic import ElasticManager
    mgr = ElasticManager(1, "x", [], restart_backoff=0.05,
                         restart_backoff_cap=0.2, backoff_reset_s=999.0)
    mgr._gen_start = time.time()
    assert mgr._respawn_backoff(healthy=True) == 0.0
    assert mgr._backoff_level == 0
    d1 = mgr._respawn_backoff(healthy=False)
    d2 = mgr._respawn_backoff(healthy=False)
    assert (d1, d2) == (0.05, 0.1)         # crash curve escalates
    assert mgr._respawn_backoff(healthy=True) == 0.0
    assert mgr._backoff_level == 0          # ... and healthy resets it


def test_elastic_manager_backs_off_between_restarts(tmp_path):
    from paddle_tpu.distributed.elastic import ElasticManager
    script = tmp_path / "crash.py"
    script.write_text("import sys; sys.exit(3)\n")
    mgr = ElasticManager(1, str(script), [], max_restarts=2,
                         poll_interval=0.02, restart_backoff=0.25,
                         restart_backoff_cap=2.0, backoff_reset_s=999.0)
    t0 = time.monotonic()
    rc = mgr.run()
    dt = time.monotonic() - t0
    assert rc == 3
    assert mgr.restarts == 3               # budget spent
    # two respawns happened → at least base + 2*base of damping
    assert dt >= 0.25 + 0.5, dt
    assert mgr._backoff_level == 2


# -- engine failure semantics -------------------------------------------


def tiny_gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def dense_ref(net, prompt, n_new):
    import jax.numpy as jnp
    out = net.generate(jnp.asarray([prompt]), max_new_tokens=n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_engine_deadline_resolves_future_and_keeps_serving():
    from paddle_tpu.inference.llm import LLMEngine
    net = tiny_gpt()
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=16) as eng:
        doomed = eng.submit([1, 2, 3], max_new_tokens=8,
                            deadline=0.0005)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
        ok = eng.submit([7, 8, 9], max_new_tokens=3).result(timeout=60)
        assert ok["output_ids"] == dense_ref(net, [7, 8, 9], 3)
    assert len(eng._free_pages) == eng.num_pages - 1


def test_engine_sheds_on_bounded_queue_overflow():
    from paddle_tpu.inference.llm import AdmissionShed, LLMEngine
    net = tiny_gpt()
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=64,
                   prefill_chunk=16, max_pending=2) as eng:
        # the first submissions pin the loop in compile + decode; the
        # burst behind them overflows max_pending=2 and must shed
        futs = [eng.submit([i + 1, i + 2, i + 3], max_new_tokens=16)
                for i in range(8)]
        outcomes = {"ok": 0, "shed": 0}
        for f in futs:
            try:
                f.result(timeout=120)
                outcomes["ok"] += 1
            except AdmissionShed as e:
                assert "admission queue full" in str(e)
                outcomes["shed"] += 1
        assert outcomes["shed"] >= 1, outcomes
        assert outcomes["ok"] >= 1, outcomes
        assert outcomes["ok"] + outcomes["shed"] == 8
    assert len(eng._free_pages) == eng.num_pages - 1


def test_generate_batch_wider_than_max_pending_never_sheds():
    """generate() applies its own backpressure window, so the bounded
    admission queue can't shed the tail of a wide batch (review
    finding)."""
    from paddle_tpu.inference.llm import LLMEngine
    net = tiny_gpt()
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=16, max_pending=2) as eng:
        outs = eng.generate(prompts, max_new_tokens=2)
    assert len(outs) == 6
    for p, o in zip(prompts, outs):
        assert o["output_ids"] == dense_ref(net, p, 2), (p, o)
    assert len(eng._free_pages) == eng.num_pages - 1


def test_device_retry_starts_a_fresh_admission_cycle():
    """admit_timeout bounds time-in-queue per admission cycle, not
    total request age — a device retry of an old request must not be
    instantly failed AdmissionTimeout (review finding)."""
    from paddle_tpu.inference.llm import LLMEngine
    net = tiny_gpt()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16, admit_timeout=0.3,
                    device_retry_budget=1)
    try:
        real = eng._decode_fn
        state = {"n": 0}

        def slow_then_flaky(*a, **kw):
            state["n"] += 1
            if state["n"] == 1:
                # make the request OLDER than admit_timeout before its
                # device error, without ever occupying the queue
                time.sleep(0.5)
                raise RuntimeError("transient PJRT failure")
            return real(*a, **kw)

        eng._decode_fn = slow_then_flaky
        out = eng.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)
        assert out["output_ids"] == dense_ref(net, [1, 2, 3], 3)
    finally:
        eng.close()
    assert len(eng._free_pages) == eng.num_pages - 1


def test_engine_cancel_resolves_and_frees_pages():
    from paddle_tpu.inference.llm import LLMEngine, RequestCancelled
    net = tiny_gpt()
    with LLMEngine(net, max_seqs=4, page_size=4, num_pages=64,
                   prefill_chunk=16) as eng:
        futs = [eng.submit([i + 1, i + 2], max_new_tokens=64)
                for i in range(4)]
        assert all(hasattr(f, "request_id") for f in futs)
        time.sleep(0.3)                    # let decode start
        for f in futs:
            eng.cancel(f.request_id)
        for f in futs:
            try:
                f.result(timeout=120)      # finished before cancel: ok
            except RequestCancelled:
                pass
        # unknown / already-resolved ids are a polite no-op
        assert eng.cancel(futs[0].request_id) is False
        assert eng.cancel(10 ** 9) is False
    assert len(eng._free_pages) == eng.num_pages - 1


def test_cancel_wins_over_a_simultaneous_device_error():
    """An accepted cancel() resolves RequestCancelled even when a
    device error delivers the outcome (review finding: the raw device
    exception used to leak to the cancelled caller)."""
    from paddle_tpu.inference.llm import LLMEngine, RequestCancelled
    net = tiny_gpt()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16)
    try:
        box = {}

        def dying(*a, **kw):
            # cancel lands while the request is slotted, in the same
            # tick the device dies — deterministic interleaving
            eng.cancel(box["fut"].request_id)
            raise RuntimeError("device died")

        eng._decode_fn = dying
        box["fut"] = eng.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(RequestCancelled):
            box["fut"].result(timeout=120)
    finally:
        eng.close()
    assert len(eng._free_pages) == eng.num_pages - 1


def test_engine_admission_timeout_is_typed_not_an_infinite_spin():
    from paddle_tpu.inference.llm import AdmissionTimeout, LLMEngine
    net = tiny_gpt()
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=64,
                   prefill_chunk=16, admit_timeout=0.15) as eng:
        hog = eng.submit([1, 2, 3], max_new_tokens=64)
        starved = eng.submit([4, 5, 6], max_new_tokens=4)
        with pytest.raises(AdmissionTimeout, match="admit_timeout"):
            starved.result(timeout=120)
        assert starved.exception().args    # typed + described
        assert hog.result(timeout=120)["output_ids"]
    assert len(eng._free_pages) == eng.num_pages - 1


def test_engine_device_retry_budget_reproduces_token_stream():
    """A device error mid-request re-admits it (budget) and the retry
    regenerates the IDENTICAL stream — the nonce pins the sampling
    keys, so a retry is invisible in the output."""
    from paddle_tpu.inference.llm import LLMEngine
    net = tiny_gpt()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16, device_retry_budget=2)
    try:
        real = eng._decode_fn
        state = {"n": 0}

        def flaky(*a, **kw):
            state["n"] += 1
            if state["n"] == 2:            # fail the 2nd decode step
                raise RuntimeError("transient PJRT failure")
            return real(*a, **kw)

        eng._decode_fn = flaky
        out = eng.submit([1, 2, 3, 4], max_new_tokens=6,
                         temperature=0.8).result(timeout=120)
        assert out["output_ids"] == run_clean(net, [1, 2, 3, 4], 6)
        assert not out["truncated"]
        assert eng.health == "healthy"     # success reset the streak
    finally:
        eng.close()
    assert len(eng._free_pages) == eng.num_pages - 1


def run_clean(net, prompt, n_new):
    """Reference stream from an un-faulted engine (seeded sampling)."""
    from paddle_tpu.inference.llm import LLMEngine
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=16) as eng:
        return eng.submit(prompt, max_new_tokens=n_new,
                          temperature=0.8).result(
                              timeout=120)["output_ids"]


def test_spec_engine_mixed_dispatch_error_reclaims_pages_and_budgets():
    """A device error in a speculative engine's mixed dispatch (the
    prompt's chunks through both models) must reclaim the pages
    allocated at admission AND consume the request's device-retry
    budget (the slot table owns the request before allocation)."""
    from paddle_tpu.inference.llm import LLMEngine
    pt.seed(0)
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     attention_dropout=0.0)
    net = GPTForCausalLM(cfg)
    pt.seed(0)
    dcfg = gpt_config("gpt2-small", num_layers=1, hidden_size=32,
                      num_heads=2, vocab_size=97,
                      max_position_embeddings=64, hidden_dropout=0.0,
                      attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg)
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=32,
                    prefill_chunk=16, draft_net=draft,
                    spec_tokens=2, device_retry_budget=1)
    try:
        real = eng._mixed_fn
        state = {"n": 0}

        def flaky(*a, **kw):
            state["n"] += 1
            if state["n"] == 1:
                raise RuntimeError("transient PJRT failure")
            return real(*a, **kw)

        eng._mixed_fn = flaky
        out = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4).result(
            timeout=120)
        assert out["output_ids"]           # retried and completed
        # a budget-0 engine propagates the error instead
        state["n"] = 0
        eng.device_retry_budget = 0
        eng._mixed_fn = flaky
        with pytest.raises(RuntimeError, match="transient"):
            eng.submit([6, 7, 8], max_new_tokens=2).result(timeout=120)
    finally:
        eng.close()
    assert len(eng._free_pages) == eng.num_pages - 1, \
        "mixed dispatch error leaked KV pages"
    assert eng._n_queued == 0


def test_engine_health_walks_to_draining_and_sheds():
    from paddle_tpu.inference.llm import AdmissionShed, LLMEngine
    net = tiny_gpt()
    # a lone prompt's chunk rides the mixed dispatch: the patched site
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16, degraded_after=1,
                    drain_after=2)
    try:
        real = eng._mixed_fn

        def broken(*a, **kw):
            raise RuntimeError("device wedged")

        eng._mixed_fn = broken
        for i in range(2):                 # one error per submission
            with pytest.raises(RuntimeError, match="wedged"):
                eng.submit([1, 2, 3], max_new_tokens=2).result(
                    timeout=60)
        deadline = time.monotonic() + 30
        while eng.health != "draining" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.health == "draining"
        # draining: new submissions shed at the submit boundary
        with pytest.raises(AdmissionShed, match="draining"):
            eng.submit([4, 5], max_new_tokens=2).result(timeout=60)
        # operator recovery: reset + fixed device → serving again
        eng._mixed_fn = real
        eng.reset_health()
        assert eng.health == "healthy"
        out = eng.submit([7, 8, 9], max_new_tokens=3).result(timeout=60)
        assert out["output_ids"] == dense_ref(net, [7, 8, 9], 3)
    finally:
        eng.close()
    assert len(eng._free_pages) == eng.num_pages - 1


def test_healthz_surfaces_engine_health_state():
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.observability.server import DebugServer
    net = tiny_gpt()
    srv = DebugServer(port=0).start()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["status"] == "ok"
        assert "healthy" in body["components"].values()
        # draining flips /healthz to 503 (balancer pulls the process)
        eng._health = "draining"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=30)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "draining"
        eng.reset_health()
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
        eng.close()
        # a closed engine disappears from the health listing
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["status"] == "ok"
        assert not body.get("components")
    finally:
        eng.close()
        srv.stop()
