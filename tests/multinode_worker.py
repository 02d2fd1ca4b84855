"""Rank body for tests/test_multinode_elastic.py: a 2-process DP
training job under the multi-node NodeAgent launcher, with
step-granular AutoCheckpoint and cross-rank resume-step agreement.

Run (by the NodeAgent): python multinode_worker.py <workdir> <steps>

Env knobs (set by the test):
  MN_PREEMPT  "s@g[,s@g...]" — after committing step s while in
              generation g, exit RESTART_EXIT_CODE (graceful
              preemption; the agent restarts budget-free).
  MN_CRASH    "s@g" — crash hard (exit 3) BEFORE committing step s in
              generation g (burns the failure budget).

Rank 0 appends "step loss generation" per completed step to
<workdir>/losses.txt; the last line per step is the authoritative one
(steps re-run after a mid-epoch kill legitimately appear twice).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _parse_points(spec):
    out = set()
    for part in (spec or "").split(","):
        part = part.strip()
        if part:
            s, g = part.split("@")
            out.add((int(s), int(g)))
    return out


def main(workdir: str, total_steps: int):
    import jax
    # a child of a CPU test never takes a device: pin CPU in-code
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import nn, parallel
    from paddle_tpu.distributed import elastic
    from paddle_tpu.io.checkpoint import AutoCheckpoint

    parallel.init_parallel_env()
    rank = jax.process_index()
    gen = elastic.restart_count()
    preempt_at = _parse_points(os.environ.get("MN_PREEMPT"))
    crash_at = _parse_points(os.environ.get("MN_CRASH"))

    mesh = parallel.init_mesh(dp=2)
    pt.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4))
    model = pt.Model(net)
    model.prepare(optimizer=pt.optimizer.AdamW(learning_rate=1e-2,
                                               parameters=net),
                  loss=nn.CrossEntropyLoss())
    parallel.distributed_model(model, mesh=mesh)

    # ONE shared checkpoint directory for all ranks — orbax's native
    # multi-process mode: replicated trees are written once by the
    # primary process, finalization is atomic, and latest_step() is
    # therefore consistent on every rank after any kill. (Per-rank
    # directories are wrong here: each rank's manager would run its own
    # global sync with the primary writing nothing into the others'
    # dirs.)
    acp = AutoCheckpoint.for_model(os.path.join(workdir, "ckpt"), model)

    def agree(local_latest: int) -> int:
        # with a shared manager every rank already sees the same latest
        # step; the allgather-min remains as a guard (and covers
        # non-shared layouts), logging each rank's resume decision
        from jax.experimental import multihost_utils
        steps = multihost_utils.process_allgather(
            np.asarray([local_latest], np.int32))
        agreed = int(np.min(steps))
        with open(os.path.join(workdir, f"agree_rank{rank}.log"),
                  "a") as f:
            f.write(f"gen={gen} local={local_latest} all={steps.tolist()}"
                    f" agreed={agreed}\n")
        return agreed

    loss_path = os.path.join(workdir, "losses.txt")
    for step in acp.epochs(total_steps, agree_step=agree):
        rng = np.random.RandomState(1000 + step)  # data keyed by step
        x = rng.randn(8, 16).astype(np.float32)
        y = rng.randint(0, 4, (8, 1))
        if (step, gen) in crash_at:
            os._exit(3)  # hard failure before the commit: step is lost
        logs = model.train_batch([x], [y])
        if rank == 0:
            with open(loss_path, "a") as f:
                f.write(f"{step} {float(logs['loss']):.8f} {gen}\n")
        acp.commit(step)
        if (step, gen) in preempt_at:
            sys.exit(elastic.RESTART_EXIT_CODE)
    print("done", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
