"""paddle_tpu.device — device management facade.

Reference: python/paddle/device/ (set_device/get_device/
is_compiled_with_*, cuda streams/events under device/cuda/). On TPU the
runtime owns streams — XLA schedules compute/transfer overlap itself —
so Stream/Event become synchronization-scope facades over
block_until_ready, kept for API familiarity rather than scheduling
control (SURVEY.md §2.4: no comm streams, no c_sync_* ordering ops).

DECISION RECORD — the reference's L2 platform-runtime surface and
where each piece lands here (SURVEY.md §1 L2):

- ``Place`` / ``DeviceContextPool`` (platform/place.h,
  device_context.h:277): a Place is ``jax.Device``; the context pool
  is the PJRT client, one per backend, owned by jax. No pool facade —
  every jax.Array carries its device, so context lookup by place has
  nothing left to do.
- Streams/events (``CUDADeviceContext`` streams, ``c_sync_*`` ops,
  stream-safe allocator): XLA:TPU executes one program at a time with
  compiler-scheduled async copies; PJRT exposes completion futures,
  not streams. The Stream/Event classes below are scope facades; the
  ordering the reference gets from stream analysis the compiler gets
  from data dependence. Rejected: surfacing PJRT execute futures as
  user streams — nothing the XLA scheduler doesn't already do.
- Dynamic loader (platform/dynload/dynamic_loader.cc): vendor-lib
  dlopen lives exactly once, in the serving predictor's plugin loader
  (native/predictor.cc dlopen + ``inference.default_plugin()``
  discovery order: PT_PJRT_PLUGIN env, then the installed libtpu).
- Device-plugin interface (phi/backends/device_manager.h:116
  ``DeviceManager`` / custom_device.cc:38 ``CustomDevice``): the PJRT
  C API *is* the plugin ABI — any vendor .so exporting GetPjrtApi is
  a backend, loadable in-process by jax (jax_plugins entry point) or
  by the native predictor (set_pjrt_plugin). We deliberately add no
  second registration layer on top.
- ``InitDevices`` / global flags / enforce: jax initializes lazily;
  flags live in paddle_tpu.flags (typed, env-overridable); error
  contracts are Python exceptions (utils/enforce analog)."""

from __future__ import annotations

from typing import Optional

import jax


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str) -> str:
    """ref: paddle.device.set_device("gpu:0") → here "tpu"/"cpu".
    Single-controller jax places by sharding, not a global default;
    this validates the request and returns the canonical name."""
    name = device.split(":")[0]
    plats = {d.platform for d in jax.devices()}
    if name not in plats:
        raise ValueError(
            f"device {device!r} not available; have {sorted(plats)}")
    return get_device()


def device_count() -> int:
    return jax.device_count()


def synchronize(device: Optional[str] = None) -> None:
    """Block until all outstanding device work is complete
    (ref: paddle.device.cuda.synchronize)."""
    # a tiny computation barriers each device's stream
    for d in jax.devices():
        jax.block_until_ready(jax.device_put(jax.numpy.zeros(()), d) + 0)


class Event:
    """ref: device/cuda/Event — record/synchronize/elapsed via host
    timestamps + device barriers (XLA has no user event objects)."""

    def __init__(self):
        self._t = None

    def record(self):
        import time
        synchronize()
        self._t = time.perf_counter()

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end: "Event") -> float:
        """milliseconds between two recorded events."""
        if self._t is None or end._t is None:
            raise RuntimeError("record() both events first")
        return (end._t - self._t) * 1e3


class Stream:
    """ref: device/cuda/Stream — a no-op scope: XLA owns stream
    assignment; kept so portable code using `with Stream():` runs."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def synchronize(self):
        synchronize()


# -- round-4 surface completion (tools/api_coverage.py) ---------------------
from .fill_r4 import (  # noqa: E402,F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, IPUPlace, MLUPlace, NPUPlace,
    TPUPlace, XPUPlace, get_all_custom_device_type,
    get_available_custom_device, get_available_device,
    get_cudnn_version, is_compiled_with_cinn, is_compiled_with_cuda,
    is_compiled_with_ipu, is_compiled_with_mlu, is_compiled_with_npu,
    is_compiled_with_rocm, is_compiled_with_xpu)
