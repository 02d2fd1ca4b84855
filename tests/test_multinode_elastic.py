"""Multi-node launcher + elastic across simulated hosts (VERDICT r3
item 5): two NodeAgent process groups as "nodes", whole-node SIGKILL →
peer-lost detection → HOLD until the node is rescheduled → rendezvous
rotation → lossless resume with loss parity; plus two consecutive
graceful preemptions proving the budget-free path at generation depth
≥ 2 (ref: launch/controllers/collective.py Pod watch;
fleet/elastic/manager.py:131 etcd watcher)."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # smoke tier skips (tools/ci.sh --smoke)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multinode_worker.py")
TOTAL = 8


def _agent(node_rank, rdzv_dir, workdir, max_restarts=0, env_extra=None):
    """Launch one node agent in its own session (so a 'node loss' can
    SIGKILL the whole process group, agent + ranks, like a VM eviction)."""
    # agents never touch a device; belt-and-braces pin so no generation
    # can ever take one (workers also pin CPU in-code, see
    # multinode_worker.py)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "2", "--node_rank", str(node_rank),
         "--nproc_per_node", "1", "--rdzv_dir", rdzv_dir,
         "--max_restarts", str(max_restarts), "--node_timeout", "4",
         WORKER, workdir, str(TOTAL)],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def _read_losses(path):
    """step → last written loss (re-run steps legitimately repeat)."""
    out = {}
    if os.path.exists(path):
        for line in open(path):
            s, v, _gen = line.split()
            out[int(s)] = float(v)
    return out


def _wait(proc, timeout=240):
    """A bound of its own; a job that outlives it is killed (whole
    session) and fails the test instead of holding the suite."""
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        raise AssertionError(
            f"node agent still running after {timeout}s:\n"
            f"{out.decode()[-2000:]}")
    return proc.returncode, out.decode()


def _run_job(tmp_path, tag, max_restarts=0, env_extra=None):
    rdzv = str(tmp_path / f"rdzv_{tag}")
    work = str(tmp_path / f"work_{tag}")
    os.makedirs(work)
    agents = [_agent(n, rdzv, work, max_restarts, env_extra)
              for n in range(2)]
    results = [_wait(a) for a in agents]
    for rc, out in results:
        assert rc == 0, out
    return work, rdzv


@pytest.fixture(scope="module")
def reference_losses(tmp_path_factory):
    """Uninterrupted 2-node run — the parity baseline AND the happy-path
    completion test."""
    tmp = tmp_path_factory.mktemp("mn_ref")
    work, _ = _run_job(tmp, "ref")
    losses = _read_losses(os.path.join(work, "losses.txt"))
    assert sorted(losses) == list(range(TOTAL))
    return losses


def test_uninterrupted_multinode_completes(reference_losses):
    assert len(reference_losses) == TOTAL


def test_node_loss_hold_rejoin_resume_parity(tmp_path, reference_losses):
    """SIGKILL an entire node's process group mid-training; the
    survivor flags peer-lost and HOLDs; 'rescheduling' the node (a
    fresh agent, same rendezvous dir) rotates the master and the job
    resumes from the agreed checkpoint to a loss sequence matching the
    uninterrupted run."""
    rdzv = str(tmp_path / "rdzv")
    work = str(tmp_path / "work")
    os.makedirs(work)
    # max_restarts=0 on purpose: losing a whole node is the PLATFORM's
    # fault (peer-lost) and must not consume the failure budget
    a0 = _agent(0, rdzv, work, max_restarts=0)
    a1 = _agent(1, rdzv, work, max_restarts=0)
    loss_file = os.path.join(work, "losses.txt")
    deadline = time.time() + 240
    while time.time() < deadline:
        if len(_read_losses(loss_file)) >= 3:
            break
        time.sleep(0.2)
    else:
        for a in (a0, a1):
            os.killpg(a.pid, signal.SIGKILL)
        raise AssertionError("job never reached step 3")

    os.killpg(a1.pid, signal.SIGKILL)   # the node is gone, whole group
    a1.wait()
    time.sleep(5)                       # > --node_timeout: survivor
    rc0 = a0.poll()                     # must HOLD, not exit
    assert rc0 is None, f"survivor exited {rc0} instead of holding"

    a1b = _agent(1, rdzv, work, max_restarts=0)  # platform reschedules
    rc, out = _wait(a0)
    assert rc == 0, out
    rc, out = _wait(a1b)
    assert rc == 0, out

    state = json.load(open(os.path.join(rdzv, "rdzv.json")))
    assert state["generation"] >= 1        # rendezvous rotated
    final = _read_losses(loss_file)
    assert sorted(final) == list(range(TOTAL))
    for s in range(TOTAL):
        np.testing.assert_allclose(final[s], reference_losses[s],
                                   rtol=1e-6,
                                   err_msg=f"step {s} diverged")


def test_two_consecutive_preemptions_budget_free(tmp_path,
                                                 reference_losses):
    """Graceful preemption at generation 0 AND again at generation 1,
    with max_restarts=0: both restarts must be budget-free and the job
    still completes losslessly (generation counter depth ≥ 2)."""
    work, rdzv = _run_job(tmp_path, "preempt", max_restarts=0,
                          env_extra={"MN_PREEMPT": "2@0,4@1"})
    state = json.load(open(os.path.join(rdzv, "rdzv.json")))
    assert state["generation"] == 2
    final = _read_losses(os.path.join(work, "losses.txt"))
    assert sorted(final) == list(range(TOTAL))
    for s in range(TOTAL):
        np.testing.assert_allclose(final[s], reference_losses[s],
                                   rtol=1e-6,
                                   err_msg=f"step {s} diverged")


def test_hard_crash_burns_budget_then_errors(tmp_path):
    """A non-preemption failure consumes the budget; with
    max_restarts=0 every agent must exit non-zero (ERROR), not loop."""
    rdzv = str(tmp_path / "rdzv")
    work = str(tmp_path / "work")
    os.makedirs(work)
    agents = [_agent(n, rdzv, work, max_restarts=0,
                     env_extra={"MN_CRASH": "2@0,2@1,2@2"})
              for n in range(2)]
    results = [_wait(a) for a in agents]
    assert all(rc != 0 for rc, _ in results), results


def test_rendezvous_store_unit(tmp_path):
    """FileRendezvous derivation logic, no subprocesses: generation
    stepping past flags and budget accounting by flag reason."""
    from paddle_tpu.distributed.multinode import FileRendezvous
    r0 = FileRendezvous(str(tmp_path), 0, 2)
    r1 = FileRendezvous(str(tmp_path), 1, 2)
    try:
        assert r0.next_generation() == 0
        r0.publish(0, "127.0.0.1:1", 1)
        r1.request_restart(0, "preempt", 67)
        assert r0.next_generation() == 1
        assert r0.burned_restarts(1) == 0          # preempt is free
        r0.publish(1, "127.0.0.1:2", 1)
        r0.request_restart(1, "peer-lost", -1)     # platform's fault:
        r1.request_restart(1, "preempt", 67)       # ...also free
        assert r1.next_generation() == 2
        assert r1.burned_restarts(2) == 0
        r0.request_restart(2, "failure", 3)        # genuine crash burns
        r1.request_restart(2, "peer-lost", -1)
        assert r1.next_generation() == 3
        assert r1.burned_restarts(3) == 1
        assert r0.stale_peers(timeout=60) == []    # both beating
        r1.stop()
        time.sleep(0.05)
        assert r0.stale_peers(timeout=1e-9) == [1]
    finally:
        r0.stop()
        r1.stop()


# ---- TCP rendezvous backend (VERDICT r4 item 6: clusters without a
# shared filesystem; ref: paddle/fluid/distributed/store/tcp_store.h)


def _tcp_agent(node_rank, endpoint, workdir, max_restarts=0,
               env_extra=None):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "2", "--node_rank", str(node_rank),
         "--nproc_per_node", "1", "--rdzv_backend", "tcp",
         "--rdzv_endpoint", endpoint,
         "--max_restarts", str(max_restarts), "--node_timeout", "4",
         WORKER, workdir, str(TOTAL)],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def test_tcp_rendezvous_store_unit():
    """TCPRendezvous speaks the same protocol as FileRendezvous:
    server-side ages, generation stepping, budget accounting — over
    localhost sockets, leader-hosted."""
    from paddle_tpu.distributed.launch import find_free_port
    from paddle_tpu.distributed.tcp_store import (StoreUnavailable,
                                                  TCPRendezvous)
    ep = f"127.0.0.1:{find_free_port()}"
    r0 = TCPRendezvous(ep, 0, 2)          # leader hosts the store
    r1 = TCPRendezvous(r0.endpoint, 1, 2)
    try:
        assert r0.peers_all_fresh(5.0)
        assert r1.peers_all_fresh(5.0)
        assert r0.next_generation() == 0
        r0.publish(0, "127.0.0.1:1", 1)
        assert r1.read()["master"] == "127.0.0.1:1"
        r1.request_restart(0, "preempt", 67)
        assert r0.next_generation() == 1
        assert r0.burned_restarts(1) == 0          # preempt is free
        r0.publish(1, "127.0.0.1:2", 1)
        r0.request_restart(1, "failure", 1)
        assert r1.next_generation() == 2
        assert r1.burned_restarts(2) == 1          # failure burns
        r0.mark_done(2)
        assert not r0.all_done(2)
        r1.mark_done(2)
        assert r1.all_done(2)
    finally:
        r1.stop()
        r0.stop()
    # with the server gone, clients surface StoreUnavailable
    import pytest as _pytest
    with _pytest.raises(StoreUnavailable):
        r1.read()


def test_tcp_backend_job_with_preemption(tmp_path, reference_losses):
    """End-to-end over sockets: a 2-node job with one graceful
    preemption completes losslessly on the TCP rendezvous — the
    test_multinode_elastic story with no shared filesystem."""
    from paddle_tpu.distributed.launch import find_free_port
    ep = f"127.0.0.1:{find_free_port()}"
    work = str(tmp_path / "work_tcp")
    os.makedirs(work)
    agents = [_tcp_agent(n, ep, work, max_restarts=0,
                         env_extra={"MN_PREEMPT": "2@0"})
              for n in range(2)]
    results = [_wait(a) for a in agents]
    for rc, out in results:
        assert rc == 0, out
    final = _read_losses(os.path.join(work, "losses.txt"))
    assert sorted(final) == list(range(TOTAL))
    for s in range(TOTAL):
        np.testing.assert_allclose(final[s], reference_losses[s],
                                   rtol=1e-6,
                                   err_msg=f"step {s} diverged")


def test_tcp_backend_follower_loss_hold_rejoin(tmp_path,
                                               reference_losses):
    """SIGKILL the FOLLOWER node's whole group mid-training on the TCP
    backend: the leader (who hosts the store) flags peer-lost, HOLDs,
    and the rescheduled follower rejoins through the same endpoint to
    a lossless finish."""
    from paddle_tpu.distributed.launch import find_free_port
    ep = f"127.0.0.1:{find_free_port()}"
    work = str(tmp_path / "work")
    os.makedirs(work)
    a0 = _tcp_agent(0, ep, work, max_restarts=0)
    a1 = _tcp_agent(1, ep, work, max_restarts=0)
    loss_file = os.path.join(work, "losses.txt")
    deadline = time.time() + 240
    while time.time() < deadline:
        if len(_read_losses(loss_file)) >= 3:
            break
        time.sleep(0.2)
    else:
        for a in (a0, a1):
            os.killpg(a.pid, signal.SIGKILL)
        raise AssertionError("job never reached step 3")

    os.killpg(a1.pid, signal.SIGKILL)
    a1.wait()
    time.sleep(5)                       # > --node_timeout
    assert a0.poll() is None, "leader exited instead of holding"

    a1b = _tcp_agent(1, ep, work, max_restarts=0)
    rc, out = _wait(a0)
    assert rc == 0, out
    rc, out = _wait(a1b)
    assert rc == 0, out
    final = _read_losses(loss_file)
    assert sorted(final) == list(range(TOTAL))
    for s in range(TOTAL):
        np.testing.assert_allclose(final[s], reference_losses[s],
                                   rtol=1e-6,
                                   err_msg=f"step {s} diverged")
