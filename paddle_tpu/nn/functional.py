"""nn.functional: the functional neural-net op library.

TPU-native rebuild of the reference's ``paddle.nn.functional``
(reference: python/paddle/nn/functional/{activation,conv,norm,loss,pooling,
common,input}.py, each bottoming out in phi kernels via _C_ops). Here every
op is a jnp/lax composition that XLA fuses; there is no kernel registry —
XLA *is* the kernel library (SURVEY.md §7 design stance). Convolutions and
matmuls map to the MXU via lax.conv_general_dilated / jnp.dot.

Layout: functions take ``data_format`` ("NCHW" default, matching the
reference API) and lower through lax dimension_numbers; XLA:TPU performs
its own layout assignment so no manual transposes are needed.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core import rng

# ---------------------------------------------------------------------------
# Activations (ref: python/paddle/nn/functional/activation.py)
# ---------------------------------------------------------------------------

relu = jax.nn.relu
relu6 = jax.nn.relu6
sigmoid = jax.nn.sigmoid
softplus = jax.nn.softplus
silu = jax.nn.silu
swish = jax.nn.silu
elu = jax.nn.elu
selu = jax.nn.selu
glu = jax.nn.glu
tanh = jnp.tanh


def gelu(x, approximate: bool = False):
    """Exact erf form by default, matching the reference's
    paddle.nn.functional.gelu(approximate=False) (phi/kernels gelu);
    jax.nn.gelu's own default is the tanh approximation."""
    return jax.nn.gelu(x, approximate=approximate)


def gelu_tanh(x):
    """The tanh approximation (HF gpt2's "gelu_new") as a named
    activation so model configs can select it by string."""
    return jax.nn.gelu(x, approximate=True)


def leaky_relu(x, negative_slope: float = 0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def hardsigmoid(x, slope: float = 1 / 6, offset: float = 0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def softsign(x):
    return x / (1 + jnp.abs(x))


def tanhshrink(x):
    return x - jnp.tanh(x)


def softshrink(x, threshold: float = 0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


def hardshrink(x, threshold: float = 0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def prelu(x, weight, data_format: str = "NCHW"):
    """ref: nn/functional/activation.py prelu — a weight of length C
    applies along the CHANNEL axis (1 for NC*, last for N*C), not by
    trailing-axis broadcasting (plain ``weight * x`` would silently
    scale the wrong axis for NCHW inputs)."""
    w = jnp.asarray(weight)
    if w.size > 1 and x.ndim > 1:
        axis = 1 if data_format.startswith("NC") else x.ndim - 1
        shape = [1] * x.ndim
        shape[axis] = w.size
        w = w.reshape(shape)
    return jnp.where(x >= 0, x, w * x)


def softmax(x, axis: int = -1):
    from .. import amp
    if amp.op_in_white("softmax"):
        x = x.astype(amp.compute_dtype())
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis: int = -1):
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, temperature: float = 1.0, hard: bool = False,
                   axis: int = -1):
    g = -jnp.log(-jnp.log(
        jax.random.uniform(rng.next_key(), x.shape, dtype=x.dtype,
                           minval=1e-20, maxval=1.0) + 1e-20))
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        y_hard = jax.nn.one_hot(jnp.argmax(y, axis=axis), y.shape[axis],
                                dtype=y.dtype, axis=axis)
        # straight-through: hard value forward, soft gradient backward
        y = lax.stop_gradient(y_hard - y) + y
    return y


# ---------------------------------------------------------------------------
# Linear / embedding (ref: functional/common.py linear, functional/input.py)
# ---------------------------------------------------------------------------

def linear(x, weight, bias=None):
    """y = x @ W + b with W shaped [in, out] (reference convention,
    ref: python/paddle/nn/functional/common.py linear). Under amp.auto_cast
    the matmul runs in the AMP compute dtype (bf16 → MXU)."""
    from .. import amp
    x, weight = amp.white_cast(x, weight, op="matmul")
    y = jnp.matmul(x, weight)
    if bias is not None:
        y = y + bias
    return y


def embedding(ids, weight, padding_idx: Optional[int] = None):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None].astype(out.dtype)
        out = out * mask
    return out


def one_hot(x, num_classes: int, dtype=jnp.float32):
    return jax.nn.one_hot(x, num_classes, dtype=dtype)


def label_smooth(label, epsilon: float = 0.1):
    k = label.shape[-1]
    return (1 - epsilon) * label + epsilon / k


# ---------------------------------------------------------------------------
# Convolutions (ref: python/paddle/nn/functional/conv.py → phi conv kernels)
# Weights are stored [out_c, in_c // groups, *kernel] (reference layout).
# ---------------------------------------------------------------------------

def _norm_tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    if len(v) == 1:
        return v * n
    return v


def _conv_dim_numbers(ndim: int, channels_last: bool):
    sp = "DHW"[-ndim:]
    if channels_last:
        lhs = out = "N" + sp + "C"
    else:
        lhs = out = "NC" + sp
    rhs = "OI" + sp
    return (lhs, rhs, out)


def conv_nd(x, weight, bias=None, stride=1, padding=0, dilation=1,
            groups: int = 1, data_format: str = "NCHW",
            preferred_element_type=None):
    from .. import amp
    x, weight = amp.white_cast(x, weight, op="conv2d")
    ndim = x.ndim - 2
    stride = _norm_tuple(stride, ndim)
    dilation = _norm_tuple(dilation, ndim)
    channels_last = data_format in ("NHWC", "NDHWC", "NLC", "NWC")
    if isinstance(padding, str):
        pad = padding.upper()  # "SAME"/"VALID"
    else:
        p = _norm_tuple(padding, ndim)
        pad = [(pi, pi) for pi in p]
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, _conv_dim_numbers(ndim, channels_last))
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups,
        # int8 x int8 (quant serving) must accumulate in int32
        preferred_element_type=preferred_element_type
        or jnp.result_type(x.dtype, weight.dtype))
    if bias is not None:
        if channels_last:
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups=1, data_format="NCL"):
    return conv_nd(x, weight, bias, stride, padding, dilation, groups,
                   "NLC" if data_format == "NLC" else "NCHW")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups=1, data_format="NCHW"):
    return conv_nd(x, weight, bias, stride, padding, dilation, groups,
                   data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups=1, data_format="NCDHW"):
    return conv_nd(x, weight, bias, stride, padding, dilation, groups,
                   "NDHWC" if data_format == "NDHWC" else "NCHW")


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW"):
    """Transposed conv. Weight layout [in_c, out_c // groups, kh, kw]
    (reference convention for conv2d_transpose)."""
    ndim = x.ndim - 2
    stride = _norm_tuple(stride, ndim)
    dilation = _norm_tuple(dilation, ndim)
    p = _norm_tuple(padding, ndim)
    op = _norm_tuple(output_padding, ndim)
    channels_last = data_format in ("NHWC", "NDHWC")
    lhs_spec, _, out_spec = _conv_dim_numbers(ndim, channels_last)
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, (lhs_spec, "IO" + "DHW"[-ndim:], out_spec))
    # grad-of-conv formulation: lhs_dilation implements the upsample
    k = [(weight.shape[2 + i] - 1) * dilation[i] + 1 for i in range(ndim)]
    pad = [(k[i] - 1 - p[i], k[i] - 1 - p[i] + op[i]) for i in range(ndim)]
    out = lax.conv_general_dilated(
        x, jnp.flip(weight, axis=tuple(range(2, 2 + ndim))),
        window_strides=(1,) * ndim, padding=pad,
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        if channels_last:
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW"):
    """ref: python/paddle/nn/functional/conv.py conv3d_transpose — the
    2d transposed-conv path is rank-generic (lhs_dilation upsample)."""
    return conv2d_transpose(x, weight, bias, stride, padding,
                            output_padding, dilation, groups,
                            "NDHWC" if data_format == "NDHWC" else "NCDHW")


# ---------------------------------------------------------------------------
# Pooling (ref: python/paddle/nn/functional/pooling.py)
# ---------------------------------------------------------------------------

def _pool(x, init, reduce_fn, kernel, stride, padding, data_format,
          count_include_pad=True, average=False):
    ndim = x.ndim - 2
    kernel = _norm_tuple(kernel, ndim)
    stride = _norm_tuple(stride if stride is not None else kernel, ndim)
    p = _norm_tuple(padding, ndim)
    channels_last = data_format in ("NHWC", "NDHWC", "NLC")
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0),) + tuple((pi, pi) for pi in p) + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = ((0, 0), (0, 0)) + tuple((pi, pi) for pi in p)
    out = lax.reduce_window(x, init, reduce_fn, window, strides, pads)
    if average:
        if count_include_pad:
            denom = math.prod(kernel)
            out = out / denom
        else:
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides,
                                       pads)
            out = out / counts
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0,
               return_mask=False, data_format="NCHW"):
    if return_mask:
        from .functional_fill import max_pool_with_mask
        if data_format != "NCHW":
            raise ValueError("return_mask supports NCHW only")
        k = _norm_tuple(kernel_size, 2)
        return max_pool_with_mask(x, k, _norm_tuple(stride or k, 2),
                                  _norm_tuple(padding, 2))
    return _pool(x, -jnp.inf, lax.max, kernel_size, stride, padding,
                 data_format)


def avg_pool2d(x, kernel_size, stride=None, padding=0,
               count_include_pad=True, data_format="NCHW"):
    return _pool(x, 0.0, lax.add, kernel_size, stride, padding, data_format,
                 count_include_pad=count_include_pad, average=True)


def max_pool1d(x, kernel_size, stride=None, padding=0,
               return_mask=False, data_format="NCL"):
    if return_mask:
        from .functional_fill import max_pool_with_mask
        if data_format != "NCL":
            raise ValueError("return_mask supports NCL only")
        k = _norm_tuple(kernel_size, 1)
        return max_pool_with_mask(x, k, _norm_tuple(stride or k, 1),
                                  _norm_tuple(padding, 1))
    return _pool(x, -jnp.inf, lax.max, kernel_size, stride, padding,
                 "NLC" if data_format == "NLC" else "NCHW")


def avg_pool1d(x, kernel_size, stride=None, padding=0,
               count_include_pad=True, data_format="NCL"):
    return _pool(x, 0.0, lax.add, kernel_size, stride, padding,
                 "NLC" if data_format == "NLC" else "NCHW",
                 count_include_pad=count_include_pad, average=True)


def max_pool3d(x, kernel_size, stride=None, padding=0,
               return_mask=False, data_format="NCDHW"):
    if return_mask:
        from .functional_fill import max_pool_with_mask
        if data_format != "NCDHW":
            raise ValueError("return_mask supports NCDHW only")
        k = _norm_tuple(kernel_size, 3)
        return max_pool_with_mask(x, k, _norm_tuple(stride or k, 3),
                                  _norm_tuple(padding, 3))
    return _pool(x, -jnp.inf, lax.max, kernel_size, stride, padding,
                 "NDHWC" if data_format == "NDHWC" else "NCHW")


def avg_pool3d(x, kernel_size, stride=None, padding=0,
               count_include_pad=True, data_format="NCDHW"):
    return _pool(x, 0.0, lax.add, kernel_size, stride, padding,
                 "NDHWC" if data_format == "NDHWC" else "NCHW",
                 count_include_pad=count_include_pad, average=True)


def _adaptive_1d(x, output_size, reduce_name):
    l = x.shape[-1]
    if l % output_size:
        raise ValueError(
            f"adaptive 1d pooling needs length {l} divisible by "
            f"output_size {output_size} (static-shape TPU constraint)")
    k = l // output_size
    xr = x.reshape(*x.shape[:-1], output_size, k)
    return getattr(jnp, reduce_name)(xr, axis=-1)


def adaptive_avg_pool1d(x, output_size):
    return _adaptive_1d(x, output_size, "mean")


def adaptive_max_pool1d(x, output_size):
    return _adaptive_1d(x, output_size, "max")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    out = _norm_tuple(output_size, 3)
    d, h, w = x.shape[2:5] if data_format == "NCDHW" else x.shape[1:4]
    if d % out[0] or h % out[1] or w % out[2]:
        raise ValueError(
            "adaptive 3d pooling needs divisible spatial dims "
            f"({(d, h, w)} vs {out})")
    k = (d // out[0], h // out[1], w // out[2])
    return avg_pool3d(x, k, k, 0, data_format=data_format)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    if data_format == "NCHW":
        h, w = x.shape[2], x.shape[3]
    else:
        h, w = x.shape[1], x.shape[2]
    if h % out[0] == 0 and w % out[1] == 0:
        k = (h // out[0], w // out[1])
        return avg_pool2d(x, k, k, 0, data_format=data_format)
    # general case: mean over computed bins (rare; static shapes)
    axis_h, axis_w = (2, 3) if data_format == "NCHW" else (1, 2)
    xs = jnp.split(x, [round(i * h / out[0]) for i in range(1, out[0])],
                   axis=axis_h)
    rows = []
    for xr in xs:
        cols = jnp.split(xr, [round(j * w / out[1])
                              for j in range(1, out[1])], axis=axis_w)
        rows.append(jnp.stack([c.mean(axis=(axis_h, axis_w)) for c in cols],
                              axis=-1))
    y = jnp.stack(rows, axis=-2)
    if data_format != "NCHW":
        y = jnp.moveaxis(y, 1, -1)
    return y


def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    h, w = (x.shape[2], x.shape[3]) if data_format == "NCHW" else \
        (x.shape[1], x.shape[2])
    if h % out[0] != 0 or w % out[1] != 0:
        raise NotImplementedError("adaptive_max_pool2d needs divisible dims")
    k = (h // out[0], w // out[1])
    return max_pool2d(x, k, k, 0, data_format=data_format)


# ---------------------------------------------------------------------------
# Normalization (ref: python/paddle/nn/functional/norm.py → phi kernels)
# ---------------------------------------------------------------------------

def layer_norm(x, normalized_shape, weight=None, bias=None,
               epsilon: float = 1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    # fp32 statistics for bf16 inputs (TPU numerics practice) — unless
    # the user custom_white_listed layer_norm, which FORCES the compute
    # dtype (consistent with the softmax white-list path)
    from .. import amp
    if amp.op_in_white("layer_norm"):
        xf = x = x.astype(amp.compute_dtype())
    else:
        xf = x.astype(jnp.float32) if x.dtype in (
            jnp.bfloat16, jnp.float16) else x
    mean = xf.mean(axis=axes, keepdims=True)
    var = jnp.square(xf - mean).mean(axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + epsilon)
    y = y.astype(x.dtype)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


def rms_norm(x, weight=None, epsilon: float = 1e-6):
    """RMSNorm — absent in the reference's op set at v2.3 but required by
    the modern LLM zoo; TPU-first addition."""
    xf = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) \
        else x
    ms = jnp.square(xf).mean(axis=-1, keepdims=True)
    y = (xf * lax.rsqrt(ms + epsilon)).astype(x.dtype)
    if weight is not None:
        y = y * weight
    return y


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training: bool = False, momentum: float = 0.9,
               epsilon: float = 1e-5, data_format: str = "NCHW"):
    """Returns (y, new_running_mean, new_running_var).

    ref: python/paddle/nn/functional/norm.py batch_norm (momentum semantics:
    running = momentum * running + (1 - momentum) * batch).
    """
    channel_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else -1
    if x.ndim == 2:
        channel_axis = 1
    axes = tuple(i for i in range(x.ndim) if i != channel_axis % x.ndim)
    if training:
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=axes)
        var = jnp.square(xf - mean.reshape(
            [-1 if i == channel_axis % x.ndim else 1
             for i in range(x.ndim)])).mean(axis=axes)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    shape = [1] * x.ndim
    shape[channel_axis % x.ndim] = -1
    y = (x - mean.reshape(shape).astype(x.dtype)) * lax.rsqrt(
        var.reshape(shape).astype(jnp.float32) + epsilon).astype(x.dtype)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y, new_rm, new_rv


def group_norm(x, num_groups: int, weight=None, bias=None,
               epsilon: float = 1e-5, data_format: str = "NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    xg = x.reshape((n, g, c // g) + x.shape[2:]).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    mean = xg.mean(axis=axes, keepdims=True)
    var = jnp.square(xg - mean).mean(axis=axes, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + epsilon)).reshape(x.shape) \
        .astype(x.dtype)
    shape = [1, c] + [1] * (x.ndim - 2)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


def instance_norm(x, weight=None, bias=None, epsilon: float = 1e-5):
    return group_norm(x, x.shape[1], weight, bias, epsilon)


def normalize(x, p: float = 2, axis: int = 1, epsilon: float = 1e-12):
    norm = jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True)
    return x / jnp.maximum(norm, epsilon)


# ---------------------------------------------------------------------------
# Dropout (ref: functional/common.py dropout — upscale_in_train default)
# ---------------------------------------------------------------------------

def dropout(x, p: float = 0.5, training: bool = True,
            mode: str = "upscale_in_train", rng_name: str = "global"):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng.next_key(rng_name), keep, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


def dropout2d(x, p: float = 0.5, training: bool = True,
              data_format: str = "NCHW"):
    if not training or p == 0.0:
        return x
    keep = 1.0 - p
    shape = (x.shape[0], x.shape[1], 1, 1) if data_format == "NCHW" else \
        (x.shape[0], 1, 1, x.shape[3])
    mask = jax.random.bernoulli(rng.next_key(), keep, shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Losses (ref: python/paddle/nn/functional/loss.py)
# ---------------------------------------------------------------------------

def _reduce(loss, reduction: str):
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    if reduction == "none":
        return loss
    raise ValueError(f"unknown reduction {reduction!r}")


def cross_entropy(logits, label, weight=None, ignore_index: int = -100,
                  reduction: str = "mean", soft_label: bool = False,
                  axis: int = -1, label_smoothing: float = 0.0):
    """ref: functional/loss.py cross_entropy (softmax_with_cross_entropy
    kernel). Accumulates in fp32 regardless of input dtype.

    Hard-label path is written as streaming logsumexp rather than
    materializing ``log_softmax`` — on a [tokens, vocab] LM loss the
    full fp32 log-probability tensor is pure HBM traffic (the
    reference's fused softmax_with_cross_entropy CUDA kernel avoids it
    the same way); XLA fuses the converts/exp into the two reductions."""
    if soft_label:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
        tgt = label.astype(jnp.float32)
        if label_smoothing:
            tgt = label_smooth(tgt, label_smoothing)
        loss = -(tgt * logp).sum(axis=axis)
        valid = None
    else:
        xf = logits.astype(jnp.float32)
        label = label.astype(jnp.int32)
        if label.ndim == xf.ndim:  # [..., 1] index form
            label = label.squeeze(axis)
        safe = jnp.where(label == ignore_index, 0, label)
        m = jax.lax.stop_gradient(
            jnp.max(xf, axis=axis, keepdims=True))
        lse = m.squeeze(axis) + jnp.log(
            jnp.sum(jnp.exp(xf - m), axis=axis))
        picked_logit = jnp.take_along_axis(
            xf, jnp.expand_dims(safe, axis), axis=axis).squeeze(axis)
        picked = picked_logit - lse            # log p[label]
        if label_smoothing:
            # mean(log_softmax) == mean(x) - lse
            smooth_term = jnp.mean(xf, axis=axis) - lse
            picked = (1 - label_smoothing) * picked + \
                label_smoothing * smooth_term
        loss = -picked
        valid = (label != ignore_index)
        loss = jnp.where(valid, loss, 0.0)
        if weight is not None:
            w = jnp.take(weight, safe)
            loss = loss * w
    if reduction == "mean" and valid is not None:
        denom = jnp.maximum(valid.sum(), 1)
        if weight is not None:
            denom = jnp.maximum((jnp.take(weight, safe) * valid).sum(), 1e-8)
        return loss.sum() / denom
    return _reduce(loss, reduction)


softmax_with_cross_entropy = cross_entropy


def nll_loss(log_probs, label, weight=None, ignore_index: int = -100,
             reduction: str = "mean"):
    label = label.astype(jnp.int32)
    safe = jnp.where(label == ignore_index, 0, label)
    loss = -jnp.take_along_axis(log_probs, safe[..., None], axis=-1) \
        .squeeze(-1)
    valid = label != ignore_index
    if weight is not None:
        loss = loss * jnp.take(weight, safe)
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return loss.sum() / jnp.maximum(valid.sum(), 1)
    return _reduce(loss, reduction)


def mse_loss(input, label, reduction: str = "mean"):
    return _reduce(jnp.square(input - label), reduction)


def l1_loss(input, label, reduction: str = "mean"):
    return _reduce(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, reduction: str = "mean",
                   delta: float = 1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta,
                     diff - 0.5 * delta)
    return _reduce(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction: str = "mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.maximum(input, eps)) +
             (1 - label) * jnp.log(jnp.maximum(1 - input, eps)))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction: str = "mean",
                                     pos_weight=None):
    logit = logit.astype(jnp.float32)
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1) * label + 1
        loss = (1 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        loss = (1 - label) * logit + max_val + \
            jnp.log1p(jnp.exp(-jnp.abs(logit)))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def kl_div(input, label, reduction: str = "mean"):
    loss = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    return _reduce(loss, reduction)


def log_loss(input, label, epsilon: float = 1e-4):
    """ref: python/paddle/nn/functional/loss.py log_loss — elementwise
    negative log likelihood of a probability input (no reduction)."""
    return -(label * jnp.log(input + epsilon) +
             (1 - label) * jnp.log(1 - input + epsilon))


def log_sigmoid(x):
    """ref: python/paddle/nn/functional/activation.py log_sigmoid —
    stable -softplus(-x) form."""
    return -softplus(-x)


def cosine_similarity(x1, x2, axis: int = 1, eps: float = 1e-8):
    dot = (x1 * x2).sum(axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


def square_error_cost(input, label):
    return jnp.square(input - label)


# ---------------------------------------------------------------------------
# Attention (ref: operators/fused/fused_attention_op.cu, fmha_ref.h —
# rebuilt as jnp einsum; Pallas flash-attention lives in paddle_tpu.ops)
# ---------------------------------------------------------------------------

def _flash_shard_spec(mesh, q_shape, k_shape):
    """PartitionSpec under which the flash kernel runs per device on a
    multi-device mesh: batch over the data axes (dp, fsdp), heads over
    tp. None when the mesh has another live axis (pp/sp/ep own their
    own manual regions) or the shapes do not divide."""
    from jax.sharding import PartitionSpec
    if any(a not in ("dp", "fsdp", "tp") for a in mesh.axis_names):
        return None
    data = math.prod(mesh.axis_size(a) for a in mesh.data_axes)
    tp = mesh.axis_size("tp")
    if q_shape[0] % data or q_shape[2] % tp or k_shape[2] % tp:
        return None
    return PartitionSpec(*mesh.batch_spec(), None,
                         "tp" if tp > 1 else None, None)


def scaled_dot_product_attention(q, k, v, attn_mask=None,
                                 dropout_p: float = 0.0,
                                 is_causal: bool = False,
                                 scale: Optional[float] = None,
                                 training: bool = True,
                                 use_flash: bool = True):
    """q,k,v: [batch, seq, heads, head_dim] (TPU-friendly BSHD layout).

    Dispatches to the Pallas flash-attention kernel (paddle_tpu.ops)
    when the configuration allows — the TPU analog of the reference's
    fused attention (operators/fused/fused_attention_op.cu); otherwise
    runs the XLA-fused reference math below.
    """
    from .. import amp
    q, k, v = amp.white_cast(q, k, v, op="attention")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    from ..core import flags as _flags
    if use_flash and _flags.get_flag("flash_attention"):
        from ..ops.flash_attention import (flash_attention,
                                           flash_attention_available)
        if flash_attention_available(q.shape, k.shape, attn_mask,
                                     dropout_p, training,
                                     is_causal=is_causal):
            flash = functools.partial(flash_attention, causal=is_causal,
                                      sm_scale=scale)
            from ..parallel.mesh import get_mesh
            mesh = get_mesh(required=False)
            if mesh is None or mesh.size == 1:
                return flash(q, k, v)
            # a Mosaic kernel cannot be partitioned by GSPMD ("wrap the
            # call in a shard_map"): attention is independent per batch
            # row and per head, so each device runs the kernel on its
            # own (data-axes batch, tp heads) block. Layouts this does
            # not cover take the XLA math below.
            spec = _flash_shard_spec(mesh, q.shape, k.shape)
            if spec is not None:
                return jax.shard_map(flash, mesh=mesh.mesh,
                                     in_specs=(spec, spec, spec),
                                     out_specs=spec,
                                     check_vma=False)(q, k, v)
    if q.shape[2] != k.shape[2]:  # grouped-query: materialize kv repeat
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        ql, kl = q.shape[1], k.shape[1]
        causal = jnp.tril(jnp.ones((ql, kl), dtype=bool), kl - ql)
        logits = jnp.where(causal, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        probs = dropout(probs, dropout_p, training=training)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# Shape / misc (ref: functional/common.py)
# ---------------------------------------------------------------------------

def pad(x, pad: Sequence[int], mode: str = "constant", value: float = 0.0,
        data_format: str = "NCHW"):
    """Paddle pad semantics: ``pad`` lists (before, after) for the last
    len(pad)//2 dims, innermost first when len(pad) == 2*spatial."""
    if len(pad) % 2 != 0:
        raise ValueError("pad length must be even")
    n = len(pad) // 2
    # innermost dimension first: pad[0:2] applies to the innermost
    # SPATIAL dim (the reference's (left, right, top, bottom)
    # convention); data_format says where the spatial dims live
    pairs = [(pad[2 * i], pad[2 * i + 1]) for i in reversed(range(n))]
    channels_last = data_format in ("NHWC", "NDHWC", "NLC", "NWC")
    if channels_last and n == x.ndim - 2:
        cfg = [(0, 0)] + pairs + [(0, 0)]
    else:
        cfg = [(0, 0)] * (x.ndim - n) + pairs
    jmode = {"constant": "constant", "reflect": "reflect",
             "replicate": "edge", "circular": "wrap"}[mode]
    if jmode == "constant":
        return jnp.pad(x, cfg, mode="constant", constant_values=value)
    return jnp.pad(x, cfg, mode=jmode)


def pad3d(x, paddings, mode: str = "constant", value: float = 0.0,
          data_format: str = "NCDHW"):
    """5-D pad (ref: legacy_api.yaml pad3d; nn/functional/common.py pad
    dispatches here for NCDHW). ``paddings``: 6 ints, innermost first
    (w_before, w_after, h_before, h_after, d_before, d_after)."""
    if x.ndim != 5:
        raise ValueError(f"pad3d expects a 5-D tensor, got {x.ndim}-D")
    return pad(x, list(paddings), mode=mode, value=value,
               data_format=data_format)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    p = _norm_tuple(paddings, 2)
    d = _norm_tuple(dilations, 2)
    n, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x, k, s, [(p[0], p[0]), (p[1], p[1])], rhs_dilation=d,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return patches.reshape(n, patches.shape[1], -1)


def _interp_axis_align_corners(x, out_len: int, axis: int):
    """1-D linear resize with align_corners=True semantics along ``axis``:
    output i samples input coord i*(in-1)/(out-1)."""
    in_len = x.shape[axis]
    if out_len == 1 or in_len == 1:
        idx = jnp.zeros((out_len,), jnp.int32)
        return jnp.take(x, idx, axis=axis)
    coords = jnp.linspace(0.0, in_len - 1, out_len)
    lo = jnp.floor(coords).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, in_len - 1)
    frac = (coords - lo).astype(x.dtype)
    shape = [1] * x.ndim
    shape[axis] = out_len
    frac = frac.reshape(shape)
    x_lo = jnp.take(x, lo, axis=axis)
    x_hi = jnp.take(x, hi, axis=axis)
    return x_lo * (1 - frac) + x_hi * frac


def interpolate(x, size=None, scale_factor=None, mode: str = "nearest",
                align_corners: bool = False, data_format: str = "NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError
    n, c, h, w = x.shape
    if size is None:
        sf = _norm_tuple(scale_factor, 2)
        size = (int(h * sf[0]), int(w * sf[1]))
    size = _norm_tuple(size, 2)
    if align_corners and mode in ("bilinear", "linear"):
        out = _interp_axis_align_corners(x, size[0], 2)
        return _interp_axis_align_corners(out, size[1], 3)
    if align_corners and mode == "bicubic":
        raise NotImplementedError(
            "bicubic align_corners=True is not supported; use bilinear")
    method = {"nearest": "nearest", "bilinear": "bilinear",
              "bicubic": "bicubic"}[mode]
    xt = jnp.moveaxis(x, 1, -1)
    out = jax.image.resize(xt, (n, size[0], size[1], c), method=method)
    return jnp.moveaxis(out, -1, 1)


def upsample(x, size=None, scale_factor=None, mode: str = "nearest",
             align_corners: bool = False, data_format: str = "NCHW"):
    """ref: nn/functional/common.py upsample — interpolate alias."""
    return interpolate(x, size=size, scale_factor=scale_factor, mode=mode,
                       align_corners=align_corners,
                       data_format=data_format)


def sequence_mask(lengths, maxlen=None, dtype="bool"):
    """[..., maxlen] mask of positions < length (ref: fluid/layers
    sequence_mask — the LoD → dense-mask bridge; pairs with
    io.pad_sequence)."""
    from ..core import dtype as dtype_mod
    lengths = jnp.asarray(lengths)
    if maxlen is None:
        maxlen = int(jnp.max(lengths))  # host read; pass maxlen under jit
    pos = jnp.arange(maxlen, dtype=lengths.dtype)
    mask = pos < lengths[..., None]
    return mask if dtype == "bool" else mask.astype(dtype_mod.dtype(dtype))


def channel_shuffle(x, groups: int, data_format: str = "NCHW"):
    """ref: nn/functional/vision.py channel_shuffle (ShuffleNet)."""
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c, h, w = x.shape
    if c % groups:
        raise ValueError(f"channels {c} not divisible by {groups} groups")
    out = x.reshape(n, groups, c // groups, h, w)
    out = out.swapaxes(1, 2).reshape(n, c, h, w)
    if data_format == "NHWC":
        out = jnp.moveaxis(out, 1, -1)
    return out


def affine_grid(theta, out_shape, align_corners: bool = True):
    """Sampling grid from batched 2x3 affine matrices (ref:
    nn/functional/vision.py affine_grid; spatial transformer)."""
    theta = jnp.asarray(theta, jnp.float32)
    n, _, _ = theta.shape
    _, _, h, w = out_shape
    if align_corners:
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
    else:
        ys = (jnp.arange(h) + 0.5) * 2.0 / h - 1.0
        xs = (jnp.arange(w) + 0.5) * 2.0 / w - 1.0
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [h, w, 3]
    grid = jnp.einsum("hwk,nck->nhwc", base, theta)         # [n, h, w, 2]
    return grid


def grid_sample(x, grid, mode: str = "bilinear",
                padding_mode: str = "zeros", align_corners: bool = True):
    """Sample input at grid locations in [-1, 1] (ref:
    nn/functional/vision.py grid_sample). Vectorized gather4 + lerp —
    the same formulation as vision.ops roi_align's sampler, batched."""
    x = jnp.asarray(x, jnp.float32)
    grid = jnp.asarray(grid, jnp.float32)
    n, c, h, w = x.shape
    gx, gy = grid[..., 0], grid[..., 1]                     # [n, ho, wo]
    if align_corners:
        fx = (gx + 1.0) * (w - 1) / 2.0
        fy = (gy + 1.0) * (h - 1) / 2.0
    else:
        fx = ((gx + 1.0) * w - 1.0) / 2.0
        fy = ((gy + 1.0) * h - 1.0) / 2.0
    if padding_mode == "border":
        fx = jnp.clip(fx, 0, w - 1)
        fy = jnp.clip(fy, 0, h - 1)
    elif padding_mode == "reflection":
        # triangle wave with period 2*span: in-range values unchanged,
        # out-of-range values reflected back across the edges
        span_x = float(w - 1) if align_corners else float(w)
        span_y = float(h - 1) if align_corners else float(h)
        fx = span_x - jnp.abs(jnp.mod(fx, 2 * span_x) - span_x)
        fy = span_y - jnp.abs(jnp.mod(fy, 2 * span_y) - span_y)
        fx = jnp.clip(fx, 0, w - 1)
        fy = jnp.clip(fy, 0, h - 1)
    elif padding_mode != "zeros":
        raise ValueError(f"unknown padding_mode {padding_mode!r}")
    if mode not in ("nearest", "bilinear"):
        raise ValueError(f"grid_sample mode {mode!r} not supported "
                         f"(nearest | bilinear)")

    if mode == "nearest":
        yi = jnp.round(fy).astype(jnp.int32)
        xi = jnp.round(fx).astype(jnp.int32)
        batch = jnp.arange(n)[:, None, None]
        valid = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        v = x[batch, :, jnp.clip(yi, 0, h - 1), jnp.clip(xi, 0, w - 1)]
        v = jnp.where(valid[..., None], v, 0.0)
        return jnp.moveaxis(v, -1, 1)
    y0 = jnp.floor(fy).astype(jnp.int32)
    x0 = jnp.floor(fx).astype(jnp.int32)
    wy1, wx1 = fy - y0, fx - x0
    batch = jnp.arange(n)[:, None, None]
    out = 0.0
    for (yi, xi, wgt) in (
            (y0, x0, (1 - wy1) * (1 - wx1)),
            (y0, x0 + 1, (1 - wy1) * wx1),
            (y0 + 1, x0, wy1 * (1 - wx1)),
            (y0 + 1, x0 + 1, wy1 * wx1)):
        valid = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        v = x[batch, :, jnp.clip(yi, 0, h - 1), jnp.clip(xi, 0, w - 1)]
        v = jnp.where(valid[..., None], v, 0.0)
        out = out + v * wgt[..., None]
    return jnp.moveaxis(out, -1, 1)


# long-tail functionals live beside their layer wrappers
from .layers.extra import (alpha_dropout, celu, fold,  # noqa: E402
                           local_response_norm, maxout,
                           pairwise_distance, pixel_shuffle,
                           pixel_unshuffle, thresholded_relu)
# detection-adjacent functionals shared with vision.ops — lazy to avoid
# the nn <-> vision import cycle
def __getattr__(name):
    if name == "temporal_shift":
        from ..vision.ops import temporal_shift
        return temporal_shift
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def swiglu(x, gate=None):
    """SwiGLU (ref: later-version incubate fused_swiglu; standard LLM
    MLP gate): silu(x) * gate, or split the last dim when gate is None."""
    if gate is None:
        x, gate = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(x) * gate


# -- round-4 surface completion (tools/api_coverage.py) ---------------------
from .functional_fill import *  # noqa: E402,F401,F403
