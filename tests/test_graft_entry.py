"""Driver-artifact checks: entry() compiles, dryrun_multichip runs on the
8-device virtual mesh (what the driver does with
xla_force_host_platform_device_count=N)."""

import os
import sys

import jax
import numpy as np

import pytest

pytestmark = pytest.mark.slow  # smoke tier skips (tools/ci.sh --smoke)


def _load():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__
    return __graft_entry__


def test_entry_compiles():
    ge = _load()
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    # GPT-2-small flagship: [batch, seq, vocab] logits
    assert np.asarray(out).shape == (2, 256, 50304)
    assert np.all(np.isfinite(np.asarray(out)))


def test_dryrun_multichip_8():
    ge = _load()
    ge.dryrun_multichip(8)


def test_dryrun_multichip_4():
    ge = _load()
    ge.dryrun_multichip(4)
