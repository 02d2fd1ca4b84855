"""paddle_tpu.inference — native serving over PJRT.

Rebuild of the reference's inference API
(reference: python/paddle/inference — ``Config`` / ``create_predictor``
over the C++ AnalysisPredictor,
paddle/fluid/inference/api/analysis_predictor.h:95; C API
paddle/fluid/inference/capi_exp/). The executor here is
paddle_tpu/native/predictor.cc: a C++ PJRT client that loads a
``paddle_tpu.jit.save`` artifact (StableHLO bytecode + binary params),
compiles it once, keeps params device-resident, and serves requests with
no Python in the loop. This module is the ctypes facade plus plugin
discovery; the same .so can be linked into any C++ server directly.
"""

from __future__ import annotations

import ctypes
import json
import os
from typing import List, Optional, Sequence

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SRC = os.path.join(_NATIVE_DIR, "predictor.cc")
_SO = os.path.join(_NATIVE_DIR, "libptpredictor.so")

# codes shared with jit/__init__.py and predictor.cc
_DTYPE_BY_CODE = ["float32", "float64", "int32", "int64", "bfloat16",
                  "float16", "uint8", "int8", "bool", "uint32", "uint64",
                  "int16", "uint16"]
_CODE_BY_DTYPE = {d: i for i, d in enumerate(_DTYPE_BY_CODE)}


def _tf_include() -> Optional[str]:
    import glob
    import sysconfig
    sp = sysconfig.get_paths()["purelib"]
    for cand in glob.glob(os.path.join(sp, "tensorflow", "include")):
        if os.path.exists(os.path.join(
                cand, "xla", "pjrt", "c", "pjrt_c_api.h")):
            return cand
    return None


def _build_so() -> str:
    inc = _tf_include()
    if inc is None:
        raise RuntimeError(
            "pjrt_c_api.h not found; cannot build the native predictor")
    from ..core.native_build import build_native_lib
    return build_native_lib(_SRC, _SO, extra_flags=(f"-I{inc}",))


_lib = None


def _load_lib():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(_build_so())
        lib.ptpred_create.restype = ctypes.c_void_p
        lib.ptpred_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t]
        lib.ptpred_run.restype = ctypes.c_int
        lib.ptpred_run.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_char_p, ctypes.c_size_t]
        lib.ptpred_num_outputs.restype = ctypes.c_int
        lib.ptpred_num_outputs.argtypes = [ctypes.c_void_p]
        lib.ptpred_out_ndim.restype = ctypes.c_int
        lib.ptpred_out_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptpred_out_dim.restype = ctypes.c_int64
        lib.ptpred_out_dim.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_int]
        lib.ptpred_out_dtype.restype = ctypes.c_uint32
        lib.ptpred_out_dtype.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptpred_out_data.restype = ctypes.c_void_p
        lib.ptpred_out_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptpred_out_nbytes.restype = ctypes.c_int64
        lib.ptpred_out_nbytes.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptpred_destroy.argtypes = [ctypes.c_void_p]
        # per-request result API (thread-safe concurrent serving)
        lib.ptpred_run2.restype = ctypes.c_void_p
        lib.ptpred_run2.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_char_p, ctypes.c_size_t]
        lib.ptres_num_outputs.restype = ctypes.c_int
        lib.ptres_num_outputs.argtypes = [ctypes.c_void_p]
        lib.ptres_ndim.restype = ctypes.c_int
        lib.ptres_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptres_dim.restype = ctypes.c_int64
        lib.ptres_dim.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                  ctypes.c_int]
        lib.ptres_dtype.restype = ctypes.c_uint32
        lib.ptres_dtype.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptres_data.restype = ctypes.c_void_p
        lib.ptres_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptres_nbytes.restype = ctypes.c_int64
        lib.ptres_nbytes.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptres_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


def default_plugin() -> str:
    """PJRT plugin discovery: env override, then the installed
    libtpu."""
    p = os.environ.get("PT_PJRT_PLUGIN")
    if p:
        return p
    try:
        import libtpu
        return os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    except Exception:
        raise RuntimeError(
            "no PJRT plugin found; set PT_PJRT_PLUGIN to a plugin .so")


def default_plugin_options() -> str:
    """Client-create options for the plugin, encoded as
    'key=i:1;key=s:text' (``PT_PJRT_PLUGIN_OPTIONS``; libtpu needs
    none)."""
    return os.environ.get("PT_PJRT_PLUGIN_OPTIONS", "")


class Config:
    """ref: paddle.inference.Config — model location + runtime knobs."""

    def __init__(self, model_dir: Optional[str] = None):
        self.model_dir = model_dir
        self.plugin_path: Optional[str] = None
        self.plugin_options: Optional[str] = None

    def set_model(self, model_dir: str):
        self.model_dir = model_dir

    def set_pjrt_plugin(self, path: str, options: str = ""):
        self.plugin_path = path
        self.plugin_options = options


class _Handle:
    """Input/output tensor handle (ref: predictor.get_input_handle /
    copy_from_cpu / copy_to_cpu)."""

    def __init__(self):
        self._arr: Optional[np.ndarray] = None

    def copy_from_cpu(self, arr):
        self._arr = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        return self._arr

    def reshape(self, shape):
        if self._arr is not None:
            self._arr = self._arr.reshape(shape)


class Predictor:
    """ref: paddle.inference.Predictor over AnalysisPredictor."""

    def __init__(self, config: Config):
        if not config.model_dir:
            raise ValueError("Config.model_dir not set")
        lib = _load_lib()
        plugin = config.plugin_path or default_plugin()
        options = config.plugin_options \
            if config.plugin_options is not None else \
            default_plugin_options()
        err = ctypes.create_string_buffer(4096)
        # Bound client creation: PJRT_Client_Create can block while
        # another process holds the chip (one process per chip), which
        # would freeze the caller — run it on a helper thread and fail
        # loudly on timeout instead. The
        # stuck thread is daemonized and leaked knowingly; the process
        # stays usable. Override via PT_PJRT_CREATE_TIMEOUT (seconds).
        import threading
        timeout = float(os.environ.get("PT_PJRT_CREATE_TIMEOUT", 120))
        box = {}

        def _create():
            try:
                box["h"] = lib.ptpred_create(
                    plugin.encode(), options.encode(),
                    config.model_dir.encode(), err, len(err))
            except BaseException as e:  # re-raised on the caller thread
                box["exc"] = e

        t = threading.Thread(target=_create, daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError(
                f"PJRT client creation did not finish in {timeout:.0f}s "
                f"— another process holds the device (plugin {plugin})")
        if "exc" in box:
            raise box["exc"]
        self._h = box.get("h")
        if not self._h:
            raise RuntimeError(
                f"predictor create failed: {err.value.decode()}")
        self._lib = lib
        with open(os.path.join(config.model_dir, "meta.json")) as f:
            self._meta = json.load(f)
        n_in = len(self._meta.get("input_spec", []))
        self._in_names = [f"input_{i}" for i in range(n_in)]
        n_out = len(self._meta.get("outputs", [])) or \
            lib.ptpred_num_outputs(self._h)
        self._out_names = [f"output_{i}" for i in range(n_out)]
        self._inputs = {n: _Handle() for n in self._in_names}
        self._outputs = {n: _Handle() for n in self._out_names}

    # -- array-style API ----------------------------------------------------
    def run(self, inputs: Optional[Sequence[np.ndarray]] = None
            ) -> List[np.ndarray]:
        """Execute one request. Thread-safe when `inputs` is passed
        explicitly: each call owns its result handle (ptpred_run2) and
        ctypes releases the GIL for the duration of the native call, so
        N server threads share one predictor (the reference requires a
        predictor clone per thread — analysis_predictor.h:95; PJRT's
        re-entrant execute removes that restriction here). The
        handle-style API (get_input_handle / get_output_handle) stores
        per-predictor state and stays single-threaded."""
        lib = self._lib
        explicit_inputs = inputs
        if inputs is None:
            inputs = [self._inputs[n].copy_to_cpu()
                      for n in self._in_names]
        arrs = [np.ascontiguousarray(a) for a in inputs]
        # match the exported program's canonicalized dtypes (e.g. jax
        # lowers int64 ids to int32 without x64 mode) and validate
        # shapes — the PJRT execute path reports shape errors
        # asynchronously (or not at all on some plugins), so fail here
        exp = self._meta.get("exported_inputs")
        if exp:
            if len(arrs) != len(exp):
                raise ValueError(
                    f"expected {len(exp)} inputs, got {len(arrs)}")
            for i, (a, e) in enumerate(zip(arrs, exp)):
                es = e["shape"]  # symbolic dims serialize as strings
                if len(a.shape) != len(es) or any(
                        isinstance(d, int) and d != ad
                        for d, ad in zip(es, a.shape)):
                    raise ValueError(
                        f"input {i}: expected shape {es}, "
                        f"got {list(a.shape)}")
            arrs = [a if str(a.dtype) == e["dtype"]
                    else np.ascontiguousarray(a.astype(e["dtype"]))
                    for a, e in zip(arrs, exp)]
        n = len(arrs)
        ptrs = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
        dtypes = (ctypes.c_uint32 * n)(
            *[_CODE_BY_DTYPE[str(a.dtype)] for a in arrs])
        ndims = (ctypes.c_uint32 * n)(*[a.ndim for a in arrs])
        dims_flat: List[int] = []
        for a in arrs:
            dims_flat.extend(a.shape)
        dims = (ctypes.c_int64 * len(dims_flat))(*dims_flat)
        err = ctypes.create_string_buffer(4096)
        res = lib.ptpred_run2(self._h, ptrs, dtypes, ndims, dims, n,
                              err, len(err))
        if not res:
            raise RuntimeError(f"predictor run failed: "
                               f"{err.value.decode()}")
        try:
            outs = []
            for i in range(lib.ptres_num_outputs(res)):
                nd = lib.ptres_ndim(res, i)
                shape = tuple(lib.ptres_dim(res, i, d)
                              for d in range(nd))
                code = lib.ptres_dtype(res, i)
                nbytes = lib.ptres_nbytes(res, i)
                dtype = _DTYPE_BY_CODE[code]
                if dtype == "bfloat16":
                    import ml_dtypes
                    np_dtype = np.dtype(ml_dtypes.bfloat16)
                else:
                    np_dtype = np.dtype(dtype)
                if nbytes == 0:  # empty output: data() may be NULL
                    outs.append(np.empty(shape, np_dtype))
                    continue
                # zero-copy view of the result buffer (owned by `res`,
                # alive until ptres_destroy below), one copy out
                ptr = ctypes.cast(lib.ptres_data(res, i),
                                  ctypes.POINTER(ctypes.c_uint8))
                raw = np.ctypeslib.as_array(ptr, shape=(nbytes,))
                outs.append(raw.view(np_dtype).reshape(shape).copy())
        finally:
            lib.ptres_destroy(res)
        if explicit_inputs is None:
            # handle-style callers read these back; explicit-input
            # (thread-safe) calls skip the shared store entirely
            for n_, a in zip(self._out_names, outs):
                self._outputs[n_].copy_from_cpu(a)
        return outs

    # -- handle-style API (reference parity) --------------------------------
    def get_input_names(self):
        return list(self._in_names)

    def get_output_names(self):
        return list(self._out_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    def get_output_handle(self, name):
        return self._outputs[name]

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.ptpred_destroy(h)
            self._h = None


def create_predictor(config: Config) -> Predictor:
    """ref: paddle.inference.create_predictor."""
    return Predictor(config)


class DynamicBatcher:
    """Micro-batching front-end over a predictor.

    The reference scales serving by running one AnalysisPredictor clone
    per server thread (reference:
    paddle/fluid/inference/api/analysis_predictor.h:95 + capi_exp
    thread pools) — each clone holds its own scopes. On TPU the
    executable is compiled at a fixed batch B and the MXU wants full
    tiles, so the throughput move is the opposite: ONE predictor, many
    request threads, and a coalescer that packs up to B queued rows
    into a single device call.

    ``submit(inputs)`` (each input's leading dim = this request's row
    count) returns a Future. A worker thread drains the queue: after
    the first request arrives it waits at most ``max_delay_ms`` for
    more, packs rows up to ``max_batch``, pads the tail by repeating
    the final row (XLA shapes are static), runs once, and slices each
    request's rows back out of the outputs. Requests that would
    overflow the pack are held for the next cycle, preserving order.
    """

    def __init__(self, predictor, max_batch: Optional[int] = None,
                 max_delay_ms: float = 2.0):
        if max_batch is None:
            exp = getattr(predictor, "_meta", {}).get("exported_inputs")
            if exp and isinstance(exp[0]["shape"][0], int):
                max_batch = exp[0]["shape"][0]
            else:
                raise ValueError(
                    "max_batch not given and the artifact's leading "
                    "input dim is not a static int")
        self._pred = predictor
        self.max_batch = int(max_batch)
        self.max_delay = max_delay_ms / 1000.0
        import queue
        import threading
        self._q: "queue.Queue" = queue.Queue()
        self._held = None  # overflow request deferred to the next pack
        self._closed = False
        # makes the closed-check + put atomic against close(): no
        # submit can enqueue after the STOP sentinel, so _drain is
        # guaranteed to see every accepted request
        self._mu = threading.Lock()
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()
        # served/coalesced stats for tests and monitoring
        self.n_requests = 0
        self.n_device_calls = 0

    def submit(self, inputs: Sequence[np.ndarray]):
        from concurrent.futures import Future
        arrs = [np.ascontiguousarray(a) for a in inputs]
        rows = arrs[0].shape[0]
        if rows > self.max_batch:
            raise ValueError(
                f"request rows {rows} > max_batch {self.max_batch}")
        if any(a.shape[0] != rows for a in arrs):
            raise ValueError("all inputs must share the leading dim")
        fut: Future = Future()
        with self._mu:
            if self._closed:
                raise RuntimeError("batcher closed")
            self._q.put((arrs, rows, fut))
        return fut

    def run(self, inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Blocking convenience wrapper around submit()."""
        return self.submit(inputs).result()

    # -- worker -------------------------------------------------------------
    def _take(self, timeout):
        if self._held is not None:
            item, self._held = self._held, None
            return item
        import queue
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def _loop(self):
        import time
        while True:
            first = self._take(timeout=0.1)
            if first is None:
                if self._closed:
                    return self._drain()
                continue
            if first == "STOP":
                return self._drain()
            pack = [first]
            used = first[1]
            deadline = time.monotonic() + self.max_delay
            while used < self.max_batch:
                rest = deadline - time.monotonic()
                nxt = self._take(timeout=max(rest, 0.0))
                if nxt is None:
                    break
                if nxt == "STOP":
                    self._flush(pack, used)
                    return self._drain()
                if used + nxt[1] > self.max_batch:
                    self._held = nxt  # keep order; goes in the next pack
                    break
                pack.append(nxt)
                used += nxt[1]
            self._flush(pack, used)

    def _drain(self):
        """Serve everything accepted before close() — a graceful close
        must not drop work whose submit() already succeeded (submit's
        closed-check is atomic with the STOP put, so all queued items
        were accepted). Packs and flushes exactly like the live loop;
        a predictor error still fails only its own pack's futures, and
        no future is ever left forever-pending."""
        import queue
        leftovers = [self._held] if self._held is not None else []
        self._held = None
        while True:
            try:
                leftovers.append(self._q.get_nowait())
            except queue.Empty:
                break
        pack, used = [], 0
        for item in leftovers:
            if item == "STOP":
                continue
            if used + item[1] > self.max_batch and pack:
                self._flush(pack, used)
                pack, used = [], 0
            pack.append(item)
            used += item[1]
        if pack:
            self._flush(pack, used)

    def _flush(self, pack, used):
        try:
            # batch-build inside the guard: a shape-mismatched request
            # must fail its pack's futures, not kill the worker thread
            n_in = len(pack[0][0])
            batched = []
            for j in range(n_in):
                parts = [req[0][j] for req in pack]
                cat = np.concatenate(parts, axis=0)
                if used < self.max_batch:  # pad: repeat the last row
                    padrow = cat[-1:]
                    cat = np.concatenate(
                        [cat] + [padrow] * (self.max_batch - used),
                        axis=0)
                batched.append(cat)
            outs = self._pred.run(batched)
        except BaseException as e:
            for _, _, fut in pack:
                fut.set_exception(e)
            return
        self.n_requests += len(pack)
        self.n_device_calls += 1
        ofs = 0
        for arrs, rows, fut in pack:
            # copy: a view would pin the whole max_batch output alive
            # for as long as the caller holds its rows
            fut.set_result([o[ofs:ofs + rows].copy() for o in outs])
            ofs += rows

    def close(self):
        with self._mu:
            self._closed = True
            self._q.put("STOP")
        self._worker.join(timeout=10)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def __getattr__(name):
    # lazy: the LLM engine pulls in model/ops modules that plain
    # CNN-artifact serving never needs
    if name in ("LLMEngine", "serve_llm", "AdmissionShed",
                "AdmissionTimeout", "RequestCancelled",
                "DecodeCarry"):
        from . import llm
        return getattr(llm, name)
    if name == "PrefixCache":
        from .prefix_cache import PrefixCache
        return PrefixCache
    raise AttributeError(name)
