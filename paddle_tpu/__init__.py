"""paddle_tpu — a TPU-native deep learning framework.

Ground-up rebuild of the PaddlePaddle reference (/root/reference, see
SURVEY.md) on JAX/XLA/Pallas/pjit idioms. Top-level namespace mirrors
``paddle.*`` (reference: python/paddle/__init__.py): tensor functional API
re-exported flat, plus nn/optimizer/amp/io/metric/hapi/parallel
subpackages.
"""

from __future__ import annotations

from .version import full_version as __version__  # noqa: E402

from .core import dtype as _dtype_mod
from .core import flags as _flags_mod
from .core import rng as _rng_mod

# dtype aliases (paddle.float32 etc.)
from .core.dtype import (bfloat16, bool_, complex64, complex128,  # noqa
                         float16, float32, float64, int8, int16, int32,
                         int64, uint8, dtype, get_default_dtype,
                         set_default_dtype)

# flags / seed
get_flags = _flags_mod.get_flags
set_flags = _flags_mod.set_flags
seed = _rng_mod.seed

# flat tensor API (paddle.add, paddle.reshape, ... as in the reference)
from .tensor import *  # noqa: F401,F403
from . import tensor  # noqa: F401

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401

# late imports that depend on the above
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi.summary import flops, summary  # noqa: F401
from . import hapi  # noqa: F401
from . import parallel  # noqa: F401
from . import models  # noqa: F401

from .framework import (grad, no_grad, save, load,  # noqa: F401
                        value_and_grad)
from .framework import jit as compile  # noqa: F401  (jax.jit-style)
from . import jit  # noqa: F401  (paddle.jit module: to_static/save/load)
from . import autograd  # noqa: F401
from . import device  # noqa: F401
from . import distribution  # noqa: F401
from . import distributed  # noqa: F401
from . import observability  # noqa: F401
from . import reliability  # noqa: F401
from . import profiler  # noqa: F401
from . import quant  # noqa: F401
from . import cost_model  # noqa: F401
from . import linalg  # noqa: F401
from . import sysconfig  # noqa: F401
from . import callbacks  # noqa: F401
from . import version  # noqa: F401
from . import regularizer  # noqa: F401
from . import static  # noqa: F401
from . import fft  # noqa: F401
from . import hub  # noqa: F401
from . import incubate  # noqa: F401
from . import signal  # noqa: F401
from . import sparse  # noqa: F401
from . import text  # noqa: F401
from . import vision  # noqa: F401


def is_compiled_with_cuda() -> bool:  # API parity helper
    return False


def is_compiled_with_tpu() -> bool:
    import jax
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def device_count() -> int:
    import jax
    return jax.device_count()


def set_device(spec: str = "tpu") -> None:
    """Analog of ``paddle.set_device`` (ref: python/paddle/device/__init__.py).
    Under JAX devices are implicit; this validates the spec only."""
    if spec.split(":")[0] not in ("tpu", "cpu", "gpu"):
        raise ValueError(f"unknown device {spec!r}")


def iinfo(dtype):
    """ref: paddle.iinfo — integer dtype range info."""
    import numpy as _np
    return _np.iinfo(_np.dtype(dtype))


def finfo(dtype):
    """ref: paddle.finfo — float dtype info (works for bfloat16 via
    jax's ml_dtypes-backed finfo)."""
    import jax.numpy as _jnp
    return _jnp.finfo(dtype)

# -- round-4 surface completion (tools/api_coverage.py) ---------------------
from .compat_fill import (  # noqa: E402,F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, NPUPlace, ParamAttr, Tensor,
    batch, bool, check_shape, create_parameter, disable_signal_handler,
    disable_static, enable_static, get_cuda_rng_state, in_dynamic_mode,
    is_grad_enabled, set_cuda_rng_state, set_grad_enabled)
from .parallel import DataParallel  # noqa: E402,F401
