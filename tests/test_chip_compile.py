"""The main path's Pallas kernels, compiled by the TPU compiler for a
described (not attached) v5e at the GPT-3-1.3B head shape: 16 heads x 128,
page 16, context 2048. Interpret-mode tests cannot see what the Mosaic
lowering refuses (block shapes, tiling, VMEM); these can, at about two
seconds each and no chip time.

Only one process may load libtpu, and it keeps it until it exits: the
topology is described inside a fixture of THIS file (never at import, in a
skipif, in parametrize or in conftest), and every such test lives here.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.ops.paged_attention import paged_attention_kernel

HEADS, HEAD_DIM, PAGE, MAX_LEN = 16, 128, 16, 2048


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, say why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape", [(2, MAX_LEN, HEADS, HEAD_DIM),
                                   (4, 1024, 12, 64)],
                         ids=["1.3b-2x2048x16x128", "gpt2s-4x1024x12x64"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, shape, direction):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    _compiled(fwd if direction == "fwd" else bwd, x, x, x)


@pytest.mark.parametrize("kv_heads", [HEADS, HEADS // 4],
                         ids=["mha", "gqa16-4"])
@pytest.mark.parametrize("pool", ["bf16", "f32", "int8"])
def test_paged_attention_compiles_for_v5e(one_chip, pool, kv_heads):
    """The ragged paged-attention kernel at a mixed tick's width: 64
    prefill rows + 8 decode rows over a 4096-page pool."""
    rows, num_pages = 72, 4096

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32,
             "int8": jnp.int8}[pool]
    q = sds((rows, HEADS, HEAD_DIM), jnp.bfloat16)
    pages = sds((num_pages, PAGE, kv_heads, HEAD_DIM), dtype)
    tables = sds((rows, MAX_LEN // PAGE), jnp.int32)
    lens = sds((rows,), jnp.int32)
    if pool == "int8":
        scales = sds((num_pages, PAGE), jnp.float32)
        _compiled(
            lambda q, k, v, t, n, ks, vs: paged_attention_kernel(
                q, k, v, t, n, interpret=False, k_scales=ks, v_scales=vs),
            q, pages, pages, tables, lens, scales, scales)
    else:
        _compiled(
            lambda q, k, v, t, n: paged_attention_kernel(
                q, k, v, t, n, interpret=False),
            q, pages, pages, tables, lens)
