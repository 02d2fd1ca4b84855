"""Flash attention as a Pallas TPU kernel.

TPU-native replacement for the reference's fused CUDA attention
(reference: paddle/fluid/operators/fused/fused_attention_op.cu and
fmha_ref.h — a cuBLAS-batched QK^T → softmax → PV pipeline that
materialises the [b, h, s, s] probability tensor in HBM; and
python/paddle/nn/functional/sparse_attention.py for the long-seq path).

Design (flash attention v2 schedule, mapped to the MXU/VMEM model):
- online softmax: never materialise [s, s]; running (m, l, acc) live in
  VMEM scratch that persists across the innermost (sequential) grid dim.
- grid = (batch, q_heads, q_blocks, k_blocks); the k dimension is
  ``ARBITRARY`` (sequential) so scratch carries across it, the rest are
  ``PARALLEL``.
- causal masking skips fully-masked k-blocks via ``pl.when`` (no FLOPs
  issued) and applies an iota mask only on diagonal blocks.
- grouped-query attention: kv heads may divide q heads; the k/v index
  maps fold the head group in, so no materialised repeat_kv.
- backward = two kernels (dq; dk/dv) recomputing probabilities from the
  saved logsumexp — the standard recompute schedule that trades FLOPs
  for HBM bandwidth, which is the right trade on TPU. The D term
  (rowsum(do*o)) is computed in-kernel from the o/do blocks.
- the logsumexp residual is stored lane-replicated ([b, h, s, 128]) to
  satisfy the (8, 128) VMEM tiling of the vector units.

Layout: [batch, heads, seq, head_dim] inside the kernels (callers using
BSHD transpose at the boundary; XLA fuses the transposes).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128  # VPU lane width: row-statistics are stored lane-replicated


# Pallas kernels here compile for the TPU or raise. Interpret mode is a
# test device: tests/conftest.py sets this for the CPU suite; nothing in
# the program does, and nothing infers it from the backend.
INTERPRET = False


def _default_interpret() -> bool:
    return INTERPRET


def _pick_block(seq: int, target: int) -> int:
    """Largest power-of-two divisor of ``seq`` that is <= target."""
    b = 1
    while b * 2 <= min(seq, target) and seq % (b * 2) == 0:
        b *= 2
    return b


def _causal_mask(s, qi, kj, block_q, block_k, offset):
    """Bottom-right-aligned causal mask: query i attends keys <= i + offset
    where offset = s_k - s_q (matches the fallback's tril(..., kl - ql))."""
    row = qi * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    col = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(row >= col, s, DEFAULT_MASK_VALUE)


def _dot(a, b, trans_a=False, trans_b=False):
    dims = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, offset: int,
                block_q: int, block_k: int,
                num_k_blocks: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: a k-block strictly above the diagonal contributes nothing
    should_run = True
    if causal:
        should_run = block_q * qi + block_q - 1 + offset >= block_k * kj

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_k, d]
        v = v_ref[0, 0]
        s = _dot(q, k, trans_b=True) * sm_scale  # [bq, bk] f32
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        m_prev = m_ref[:, :1]                          # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                # rescale old state
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p.astype(v.dtype), v)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked-row guard
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l_safe),
                                         lse_ref.shape[2:])


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    nq = sq // block_q
    nk = sk // block_k

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, offset=sk - sq,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   dq_acc, delta_ref, *, sm_scale, causal, offset,
                   block_q, block_k,
                   num_k_blocks):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        delta_ref[...] = jnp.broadcast_to(
            jnp.sum(o * do, axis=-1, keepdims=True), delta_ref.shape)

    should_run = True
    if causal:
        should_run = block_q * qi + block_q - 1 + offset >= block_k * kj

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]          # [bq, 1]
        delta = delta_ref[:, :1]
        s = _dot(q, k, trans_b=True) * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        p = jnp.exp(s - lse)                # [bq, bk]
        dp = _dot(do, v.astype(jnp.float32), trans_b=True)
        ds = p * (dp - delta) * sm_scale
        dq_acc[...] += _dot(ds, k.astype(jnp.float32))

    @pl.when(kj == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    sm_scale, causal, offset, block_q, block_k,
                    num_q_blocks):
    kj = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    should_run = True
    if causal:
        should_run = block_q * qi + block_q - 1 + offset >= block_k * kj

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = jnp.sum(o * do, axis=-1, keepdims=True)   # [bq, 1]
        s = _dot(q, k, trans_b=True) * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        p = jnp.exp(s - lse)                 # [bq, bk]
        dv_acc[...] += _dot(p, do, trans_a=True)
        dp = _dot(do, v.astype(jnp.float32), trans_b=True)
        ds = p * (dp - delta) * sm_scale     # [bq, bk]
        dk_acc[...] += _dot(ds, q.astype(jnp.float32), trans_a=True)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, sm_scale, causal, block_q, block_k,
         interpret):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    nq = sq // block_q
    nk = sk // block_k

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    kvspec = pl.BlockSpec((1, 1, block_k, d),
                          lambda b_, h, i, j: (b_, h // group, j, 0))
    lspec = pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b_, h, i, j: (b_, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          offset=sk - sq, block_q=block_q, block_k=block_k,
                          num_k_blocks=nk),
        grid=(b, hq, nq, nk),
        in_specs=[qspec, kvspec, kvspec, qspec, qspec, lspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, out, do, lse)

    # dk/dv: grid iterates q-blocks sequentially per (q-head, k-block);
    # per-q-head partials are reduced over the GQA group afterwards.
    qspec_t = pl.BlockSpec((1, 1, block_q, d),
                           lambda b_, h, j, i: (b_, h, i, 0))
    kvspec_t = pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, h, j, i: (b_, h // group, j, 0))
    lspec_t = pl.BlockSpec((1, 1, block_q, _LANES),
                           lambda b_, h, j, i: (b_, h, i, 0))
    okv_t = pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, j, i: (b_, h, j, 0))

    dk_g, dv_g = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          offset=sk - sq, block_q=block_q, block_k=block_k,
                          num_q_blocks=nq),
        grid=(b, hq, nk, nq),
        in_specs=[qspec_t, kvspec_t, kvspec_t, qspec_t, qspec_t, lspec_t],
        out_specs=[okv_t, okv_t],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, out, do, lse)

    if group > 1:
        dk_g = dk_g.reshape(b, hkv, group, sk, d).sum(axis=2)
        dv_g = dv_g.reshape(b, hkv, group, sk, d).sum(axis=2)
    return dq, dk_g.astype(k.dtype), dv_g.astype(v.dtype)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, sm_scale, causal, block_q, block_k,
                interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """Memory-efficient attention. q: [b, s_q, h, d]; k/v: [b, s_k, h_kv, d]
    with h % h_kv == 0 (grouped-query). Returns [b, s_q, h, d].

    Differentiable (custom VJP with flash backward kernels). BSHD in/out;
    internally runs BHSD tiles on the MXU.
    """
    if interpret is None:
        interpret = INTERPRET
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if causal and sq > sk:
        raise ValueError(
            f"causal flash attention requires s_q <= s_k, got {sq} > {sk}: "
            "leading query rows would have no visible keys")
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, sm_scale, causal, bq, bk, interpret)
    return out.transpose(0, 2, 1, 3)


def flash_attention_available(q_shape, k_shape, attn_mask, dropout_p,
                              training, is_causal: bool = False) -> bool:
    """Whether the Pallas path handles this configuration. The kernel
    is a TPU program: off the TPU the XLA math serves (interpret mode
    only under the tests' switch)."""
    if not INTERPRET and jax.default_backend() != "tpu":
        return False
    if attn_mask is not None:
        return False
    if dropout_p > 0.0 and training:
        return False
    if len(q_shape) != 4:
        return False
    b, sq, hq, d = q_shape
    sk, hkv = k_shape[1], k_shape[2]
    if hq % hkv != 0:
        return False
    if is_causal and sq > sk:
        # degenerate: leading query rows have no visible keys (the
        # reference math yields NaN rows); keep that on the XLA path
        return False
    # tiny shapes: the reference path is cheaper than kernel launch; odd
    # lengths would force sub-(8,128) tiles that Mosaic rejects — require
    # that a full-size power-of-two block divides both sequence lengths
    return (d >= 64 and d % 8 == 0 and
            _pick_block(sq, 512) >= 128 and _pick_block(sk, 512) >= 128)
