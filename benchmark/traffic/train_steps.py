"""Training steps: an in-memory set of seeded token batches, all rows
different."""

from __future__ import annotations

import numpy as np

from . import shapes


def batches(spec: dict, seed: int, vocab: int, n: int, first: int = 0):
    """``[n, batch, seq]`` int32 tokens, uniform over the vocabulary; batch
    ``i`` depends on (seed, first + i) only."""
    b, s = int(spec["batch"]), int(spec["seq"])
    return np.stack([shapes.rng(seed, 5, first + i).integers(
        0, vocab, (b, s), dtype=np.int32) for i in range(n)])
