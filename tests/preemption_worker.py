"""Worker for tests/test_preemption.py: trains with step-granular
AutoCheckpoint + PreemptionGuard; on SIGTERM it checkpoints and exits
RESTART_EXIT_CODE; on relaunch it resumes losslessly.

Run: python preemption_worker.py <workdir> <total_steps>
Appends one line per completed step to <workdir>/losses.txt.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(workdir: str, total_steps: int):
    import jax
    # a child of a CPU test never takes a device: pin CPU in-code
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.distributed import elastic
    from paddle_tpu.io.checkpoint import AutoCheckpoint

    pt.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4))
    model = pt.Model(net)
    model.prepare(optimizer=pt.optimizer.AdamW(learning_rate=1e-2,
                                               parameters=net),
                  loss=nn.CrossEntropyLoss())

    guard = elastic.PreemptionGuard()
    # sentinel for the race-the-compile test: from here on a SIGTERM is
    # flag-only; the first train_batch (trace+compile) happens after
    open(os.path.join(workdir, "guard_installed"), "w").write("1")
    acp = AutoCheckpoint.for_model(os.path.join(workdir, "ckpt"), model)
    loss_path = os.path.join(workdir, "losses.txt")
    for step in acp.epochs(total_steps):   # step-granular range
        rng = np.random.RandomState(1000 + step)   # data keyed by step
        x = rng.randn(16, 16).astype(np.float32)
        y = rng.randint(0, 4, (16, 1))
        logs = model.train_batch([x], [y])
        with open(loss_path, "a") as f:
            f.write(f"{step} {float(logs['loss']):.8f}\n")
        acp.commit(step)
        guard.check()   # preempted? checkpoint is committed → exit 67
    print("done")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
