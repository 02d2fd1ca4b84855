"""The persistent XLA compilation cache, placed from outside.

One rule for every entry point (``Model.prepare``, a replica child, the
tools, ``bench.py``, ``chip_smoke.py``): where ``JAX_COMPILATION_CACHE_DIR``
is set, jax already points at that directory and this module sets no
other; where it is not, the cache is ONE fixed path inside the checkout
(git-ignored). The directory is part of the cache key's locality — a
path that changes from run to run never hits.
"""

from __future__ import annotations

import os
from typing import Tuple

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = None


def enable() -> Tuple[str, str]:
    """Turn the persistent cache on. Returns ``(directory, origin)``
    with origin ``"env"`` or ``"checkout"``. Idempotent."""
    global _enabled
    path = os.environ.get(ENV_VAR)
    origin = "env" if path else "checkout"
    path = path or CHECKOUT_CACHE_DIR
    if _enabled == path:
        return path, origin
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    # cache fast-compiling programs too: a serving fleet and a test
    # gate compile many small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # anything jitted earlier initialized the cache singleton as
    # disabled; re-initialize it against the directory
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    _enabled = path
    return path, origin
