"""``inference/staging.py`` (ISSUE 44): the host arrays of a dispatch packed
into one int32 vector, sent once, and cut back inside a jitted program bit
for bit. The engine's side (one transfer a decode dispatch, a staged vector
nothing rewrites) is tested beside each model: ``tests/test_issue_marks.py``
(one cache group), ``test_laguna.py`` (two), ``test_granite_hybrid.py``
(state lanes)."""
import jax
import numpy as np
import pytest

from paddle_tpu.inference.staging import StagedLayout

SLOTS = 5
TEMPS = np.array([0.0, -0.0, 1e-45, 1e-30, 0.8], np.float32)  # 1e-45: denormal
NONCES = np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max, -1, 0, 7],
                  np.int32)


def _decode_layout(widths):
    slots = (SLOTS,)
    return StagedLayout([(slots, np.int32), (slots, np.int32)]
                        + [((SLOTS, w), np.int32) for w in widths]
                        + [(slots, np.int32), (slots, np.float32)])


@pytest.mark.parametrize("widths", [(7,), (7, 3)],
                         ids=["one_group", "two_groups"])
def test_a_decode_dispatchs_arrays_come_back_bit_for_bit(widths):
    """The layout ``LLMEngine`` builds (positions, lens, a table a group,
    nonces, temperatures): what a jitted ``unpack`` returns has the shapes,
    the dtypes and the BITS of what ``pack`` was given: a temperature of
    -0.0, a denormal and 1e-30 are not converted on the way, a nonce at
    either end of int32 neither."""
    rng = np.random.default_rng(0)
    layout = _decode_layout(widths)
    arrays = [rng.integers(0, 99, SLOTS).astype(np.int32),
              rng.integers(0, 99, SLOTS).astype(np.int32)] \
        + [rng.integers(0, 2 ** 31 - 1, (SLOTS, w)).astype(np.int32)
           for w in widths] + [NONCES, TEMPS]
    packed = layout.pack(*arrays)
    assert packed.dtype == np.int32
    assert packed.shape == (4 * SLOTS + SLOTS * sum(widths),) == (layout.size,)
    got = jax.jit(layout.unpack)(layout.stage(*arrays))
    assert len(got) == len(arrays)
    for g, a in zip(got, arrays):
        assert (g.shape, g.dtype) == (a.shape, a.dtype)
        assert np.asarray(g).tobytes() == a.tobytes()
    assert np.signbit(np.asarray(got[-1])[1]) and np.asarray(got[-1])[2] > 0


def test_pack_copies_what_it_is_given():
    """A staged vector aliases none of its sources: the allocator goes on
    writing the block tables while a dispatch is queued."""
    layout = _decode_layout((4,))
    ints = np.arange(SLOTS, dtype=np.int32)
    table = np.arange(SLOTS * 4, dtype=np.int32).reshape(SLOTS, 4)
    packed = layout.pack(ints, ints, table, NONCES, TEMPS)
    assert not any(np.shares_memory(packed, a)
                   for a in (ints, table, NONCES, TEMPS))
    want = packed.copy()
    table[:] = 0
    assert np.array_equal(packed, want)


@pytest.mark.parametrize("case", ["eight_bytes", "one_byte", "shape", "dtype",
                                  "count"])
def test_a_layout_refuses_what_it_cannot_carry(case):
    """Fields are four bytes wide; ``pack`` takes exactly the arrays the
    layout names, in their shapes and dtypes."""
    if case in ("eight_bytes", "one_byte"):
        with pytest.raises(ValueError, match="4-byte"):
            StagedLayout([((3,), np.int64 if case == "eight_bytes"
                           else np.bool_)])
        return
    layout = StagedLayout([((3,), np.int32), ((3,), np.float32)])
    ints, floats = np.zeros((3,), np.int32), np.zeros((3,), np.float32)
    bad = {"shape": (ints[:2], floats), "dtype": (floats, floats),
           "count": (ints,)}[case]
    with pytest.raises(ValueError):
        layout.pack(*bad)
