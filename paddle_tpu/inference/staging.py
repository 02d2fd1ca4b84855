"""The host arrays of one dispatch as ONE transfer.

An engine program takes a handful of small host arrays a dispatch (positions,
limits, block tables, nonces, temperatures). Each ``jnp.asarray`` of one costs
about the same on the chip whatever it carries (~0.29 ms, ``chip_smoke.py
--phase staging`` repeats the reading), and the device waits for all of them:
host and device take turns. A :class:`StagedLayout` fixes, when the engine is
built, where each array lies in one flat int32 vector; a dispatch packs its
arrays into a NEW vector (:meth:`StagedLayout.pack`), sends that one
(:meth:`StagedLayout.stage`), and the jitted program cuts it back by the static
offsets (:meth:`StagedLayout.unpack`): a few slices, reshapes and a bitcast,
which fuse into what reads them. Every value reaches the program bit for bit.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import numpy as np


class StagedLayout:
    """``fields``: ``(shape, dtype)`` of each host array, in the order
    ``pack`` takes and ``unpack`` returns them. Every dtype is four bytes
    wide (int32, float32: a float's bits travel as an int32's and come back
    through ``bitcast_convert_type``, no conversion either way)."""

    def __init__(self, fields: Sequence[Tuple[Sequence[int], object]]):
        self.fields: List[Tuple[tuple, np.dtype]] = []
        self.offsets: List[int] = []
        self.size = 0
        for shape, dtype in fields:
            shape, dtype = tuple(int(n) for n in shape), np.dtype(dtype)
            if dtype.itemsize != 4 or dtype.kind not in "iuf":
                raise ValueError(
                    f"a staged field is a 4-byte number, not {dtype}")
            self.fields.append((shape, dtype))
            self.offsets.append(self.size)
            self.size += int(np.prod(shape, dtype=np.int64))

    def pack(self, *arrays: np.ndarray) -> np.ndarray:
        """The arrays as one NEW int32 vector: nothing the caller goes on
        writing (a block table) is aliased by what is sent."""
        if len(arrays) != len(self.fields):
            raise ValueError(f"{len(self.fields)} fields, "
                             f"{len(arrays)} arrays")
        parts = []
        for a, (shape, dtype) in zip(arrays, self.fields):
            if a.shape != shape or a.dtype != dtype:
                raise ValueError(f"a field {shape} {dtype} was given "
                                 f"{a.shape} {a.dtype}")
            parts.append(np.ascontiguousarray(a).reshape(-1).view(np.int32))
        return np.concatenate(parts)

    def stage(self, *arrays: np.ndarray) -> jax.Array:
        """``pack`` and the dispatch's ONE host-to-device transfer."""
        return jax.device_put(self.pack(*arrays))

    def unpack(self, staged) -> list:
        """Inside a jitted program: the fields of a staged vector, each
        in its shape and dtype."""
        out = []
        for (shape, dtype), start in zip(self.fields, self.offsets):
            n = int(np.prod(shape, dtype=np.int64))
            piece = jax.lax.slice(staged, (start,), (start + n,))
            if dtype != np.int32:
                piece = jax.lax.bitcast_convert_type(piece, dtype)
            out.append(piece.reshape(shape))
        return out
