"""A map-style DataLoader's fork pool leaves no worker behind. One pass's
pool used to shut down on its producer thread while the next pass forked on
another: a worker forked while an executor's wake-up lock was held inherited
it held, waited on it for ever at its own exit, and outlived the process with
its stdout open (a whole tier-1 run waited for such orphans until its time
limit)."""
import itertools
import multiprocessing
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.io import DataLoader, TensorDataset


def _loader(n=32):
    x = np.arange(n, dtype=np.float32)[:, None]
    return DataLoader(TensorDataset([x]), batch_size=4, shuffle=True,
                      num_workers=2, to_device=False)


@pytest.mark.parametrize("closed_early", [False, True])
def test_no_worker_outlives_back_to_back_passes(closed_early):
    before = set(multiprocessing.active_children())
    try:
        for _ in range(6):      # used to strand a pool in one pass of five
            pt.seed(19)
            it = iter(_loader())
            if closed_early:
                assert len(list(itertools.islice(it, 3))) == 3
                it.close()
            else:
                assert len(list(it)) == 8
        deadline = time.monotonic() + 20
        while set(multiprocessing.active_children()) - before \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        left = set(multiprocessing.active_children()) - before
        assert not left, f"{len(left)} pool workers still alive"
    finally:
        for p in set(multiprocessing.active_children()) - before:
            p.kill()
