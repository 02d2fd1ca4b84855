"""Test configuration: force an 8-device virtual CPU mesh so sharding /
collective tests run without TPU hardware (SURVEY.md §4: the reference's
analog is gloo-CPU collective tests + fake devices; here
xla_force_host_platform_device_count gives us N host 'chips').

The driver's command sets JAX_PLATFORMS=cpu; the config update below
makes a bare ``pytest`` do the same.

The Pallas kernels compile for the TPU or raise; the CPU suite runs
them through the Pallas interpreter by the one switch below
(``ops.flash_attention.INTERPRET``). tests/test_chip_compile.py is the
file that asks the TPU compiler.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# children a test spawns (replicas, dist workers) take what jax finds:
# hold them to the CPU the same way the driver's command holds us
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Model.prepare() turns the persistent compile cache on (core/
# compile_cache.py); the CPU suite stays hermetic and runs without it
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import importlib  # noqa: E402

# (the package re-exports a function of the same name: ask for the module)
importlib.import_module("paddle_tpu.ops.flash_attention").INTERPRET = True

assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual CPU mesh; got "
    f"{jax.devices()}")
assert jax.device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.device_count()}")


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu
    paddle_tpu.seed(42)
    np.random.seed(42)
    yield
