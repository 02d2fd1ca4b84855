"""The main path's Pallas kernels, compiled by the TPU compiler for a
described (not attached) v5e at the GPT-3-1.3B head shape: 16 heads x 128,
page 16, context 2048. Interpret-mode tests cannot see what the Mosaic
lowering refuses (block shapes, tiling, VMEM); these can, at about two
seconds each and no chip time. Two compile whole engine programs and read
their temporaries: a decode tick of GPT (the kernel must leave the pool
where it is) and the hybrid model's decode and mixed programs at the
published state shape (no copy of a layer's whole recurrent state; every
routed layer's two grouped products through the kernel).

Only one process may load libtpu, and it keeps it until it exits: the
topology is described inside a fixture of THIS file (never at import, in a
skipif, in parametrize or in conftest), and every such test lives here.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.ops.grouped_matmul import grouped_matmul
from paddle_tpu.ops.paged_attention import paged_attention_kernel
from paddle_tpu.ops.kda import kda_step_kernel
from paddle_tpu.ops.ssd import ssd_chunk_kernel, ssd_step_kernel

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


def reads_after_in_place_writes(text):
    """In a compiled, scheduled program's text: every read of a donated
    parameter (``input_output_alias`` of the module's head) that ends AFTER
    a fusion or a custom call which takes the parameter and returns its
    shape, that is, after the buffer was written in place. A read is any
    other instruction that names the parameter, or a bitcast of it; an
    asynchronous one ends at its ``-done``. ``[(parameter, reader,
    writer)]``: a sound schedule gives none. (PR 48: with the step's kernel
    in ``reason_closed_gdn``'s decode program the compiler wrote the last
    delta-rule layer's conv rows in place BEFORE a rematerialised read of
    them that fed the convolution, and the cell read ``correct`` false.)"""
    import re
    head = text[:text.index("\n")]
    numbers = {int(n) for n in re.findall(r"\}: \((\d+), \{\}", head)}
    ins = []
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*)", line)
        if m:
            ins.append((m.group(1), m.group(2)))

    def bare(shape):
        return re.sub(r"\{[^}]*\}", "", shape)

    found = []
    for par, rhs in ins:
        m = re.match(r"(\S+) parameter\((\d+)\)", rhs)
        if not m or int(m.group(2)) not in numbers:
            continue
        shape = bare(m.group(1))
        names = {par} | {n for n, r in ins if re.search(
            r" bitcast\(" + re.escape(par) + r"\)", r)}
        uses = [i for i, (n, r) in enumerate(ins) if n not in names and any(
            re.search(re.escape(x) + r"[,)]", r) for x in names)]
        writers = [i for i in uses
                   if re.search(r" (fusion|custom-call)\(", ins[i][1])
                   and shape in bare(re.split(
                       r" (?:fusion|custom-call)\(", ins[i][1])[0])]
        for i in uses:
            if i in writers:
                continue
            name, end = ins[i][0], i
            if "-start" in name:
                end = next((j for j, (_, r) in enumerate(ins) if re.search(
                    r"-done\(" + re.escape(name) + r"\)", r)), i)
            found += [(par, name, ins[w][0]) for w in writers if w < end]
    return found


_SCHEDULE = """HloModule jit_decode_fn, is_scheduled=true, input_output_alias={ {0}: (1, {}, may-alias) }

ENTRY %main (x.1: bf16[64,8], rows.1: bf16[65,3,8]) -> (bf16[65,3,8]) {
  %x.1 = bf16[64,8]{1,0} parameter(0)
  %rows.1 = bf16[65,3,8]{2,0,1:T(8,128)(2,1)} parameter(1)
READ_BEFORE
  %fusion.34 = bf16[65,3,8]{2,0,1:T(8,128)(2,1)} fusion(%rows.1, %x.1), kind=kLoop, calls=%scatter
READ_AFTER
  %conv = f32[64,8]{1,0} fusion(%x.1, %old), kind=kLoop, calls=%window
  ROOT %tuple = (bf16[65,3,8]{2,0,1:T(8,128)(2,1)}) tuple(%fusion.34)
}
"""


@pytest.mark.parametrize("before,after,found", [
    ("  %old = bf16[64,3,8]{2,0,1} fusion(%rows.1), kind=kLoop, calls=%cut",
     "", []),
    ("", "  %old = bf16[64,3,8]{2,0,1} fusion(%rows.1), kind=kLoop, "
         "calls=%cut", [("%rows.1", "%old", "%fusion.34")]),
    ("  %slice-start.48 = ((bf16[65,3,8]{2,0,1}), bf16[65,1,8]{2,0,1:S(1)}, "
     "s32[]{:S(2)}) async-start(%rows.1), calls=%cut",
     "  %old = bf16[65,1,8]{2,0,1:S(1)} async-done(%slice-start.48)",
     [("%rows.1", "%slice-start.48", "%fusion.34")]),
], ids=["read_then_write", "write_then_read", "a_read_that_spans_the_write"])
def test_a_read_of_donated_rows_after_their_write_in_place_is_found(
        before, after, found):
    """The reader of the engine programs' texts, on a schedule of five
    lines: a parameter the program's output aliases, one fusion that writes
    it in place, and a read before it, after it, or begun before and done
    after (the schedule the chip ran wrong in PR 48)."""
    text = _SCHEDULE.replace("READ_BEFORE", before).replace("READ_AFTER",
                                                            after)
    assert reads_after_in_place_writes(text) == found


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


LAYERS, POOL_PAGES = 24, 2721           # the serving benchmark's pool


@pytest.mark.parametrize("kv_heads", [HEADS, HEADS // 4],
                         ids=["mha", "gqa16-4"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_attention_over_the_stacked_pool_compiles_for_v5e(
        one_chip, pool, kv_heads):
    """The engine's signature: the whole ``[L, pages, ...]`` store and a
    traced layer index, a mixed tick's 96 rows, nothing sliced or copied
    beside the kernel."""
    rows = 96

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8}[pool]
    q = sds((rows, HEADS, HEAD_DIM), jnp.bfloat16)
    pages = sds((LAYERS, POOL_PAGES, PAGE, kv_heads, HEAD_DIM), dtype)
    tables = sds((rows, MAX_LEN // PAGE), jnp.int32)
    lens = sds((rows,), jnp.int32)
    layer = sds((), jnp.int32)
    if pool == "int8":
        scales = sds((LAYERS, POOL_PAGES, PAGE), jnp.float32)
        compiled = _compiled(
            lambda q, k, v, t, n, i, ks, vs: paged_attention_kernel(
                q, k, v, t, n, layer=i, interpret=False, k_scales=ks,
                v_scales=vs),
            q, pages, pages, tables, lens, layer, scales, scales)
        # the scale rows gathered beside the kernel: 4 bytes a table token
        budget = 4 * rows * MAX_LEN * 4
    else:
        compiled = _compiled(
            lambda q, k, v, t, n, i: paged_attention_kernel(
                q, k, v, t, n, layer=i, interpret=False),
            q, pages, pages, tables, lens, layer)
        budget = rows * HEADS * HEAD_DIM * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= budget


# (chunk, slots, heads, K/V heads, table columns, cache layers, pool pages,
# a lower bound a row) of a mixed tick's attention call in each serving cell
MIXED_CALLS = {
    "chat_closed": (64, 32, 16, 16, 128, 24, 2721, False),
    "chat_closed_hybrid": (256, 64, 32, 8, 128, 4, 8193, False),
    "reason_closed_looped": (128, 8, 16, 16, 40, 192, 321, False),
    "agent_closed_swa-full": (256, 32, 48, 8, 576, 3, 18433, False),
    "agent_closed_swa-window": (256, 32, 72, 8, 576, 9, 1569, True),
}


@pytest.mark.parametrize("cell", list(MIXED_CALLS))
def test_mixed_ticks_attention_call_compiles_for_v5e(one_chip, monkeypatch,
                                                     cell):
    """The mixed program's attention call at each serving cell's shapes:
    the chunk's rows through query tiles (``paged_attention_chunk``: the
    plan's scalars and the chunk's tables in SMEM, a tile's state, query
    block and the page buffers inside the scoped VMEM limit), the decode
    rows through the row walk, over the stacked pool with a traced layer;
    the window group's with a lower bound a row and its [288, 576] tables.
    Beside the two kernels the program keeps the plan and the query
    block's two transposes, nothing of the pool's size."""
    import importlib
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    chunk, slots, heads, kv_heads, columns, layers, pages, bound = \
        MIXED_CALLS[cell]
    rows = chunk + slots

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((rows, heads, HEAD_DIM), jnp.bfloat16)
    pool = sds((layers, pages, PAGE, kv_heads, HEAD_DIM), jnp.bfloat16)
    ints = sds((rows,), jnp.int32)

    def call(q, k, v, tables, lens, starts, layer):
        return ragged_paged_attention(
            q, k, v, tables, lens, impl="pallas", layer=layer,
            starts=starts if bound else None, n_chunk=chunk)

    compiled = _compiled(call, q, pool, pool,
                         sds((rows, columns), jnp.int32), ints, ints,
                         sds((), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "paged_attention_chunk" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 6 * rows * heads * HEAD_DIM * 2


@pytest.mark.parametrize("bound", [False, True], ids=["whole", "windowed"])
@pytest.mark.parametrize("heads,layers,pages", [(48, 3, 18433),
                                                (72, 9, 1569)],
                         ids=["full-6-a-kv-head", "window-9-a-kv-head"])
def test_decode_rows_of_many_heads_a_kv_head_compile_for_v5e(
        one_chip, heads, layers, pages, bound):
    """A decode tick's attention call in ``agent_closed_swa`` (32 rows, 48
    or 72 query heads over 8 K/V heads of 128, bf16 pages of 16 tokens,
    tables of 576 pages, with and without a lower bound a row): more than
    ``_VPU_GROUP_ROWS`` heads a K/V head, so the row walk folds on the MXU,
    a block of 16 pages at a time: the operands a view of some pages of a
    buffer slot, at a traced page, read as ``[tokens x kv_heads, d]`` in the
    pool's type, the scores ``[48 | 72, 2048]`` float32. One kernel, and
    beside it no more than the query's and the result's transposes."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, kv_heads, columns = 32, 8, 576
    pool = sds((layers, pages, PAGE, kv_heads, HEAD_DIM), jnp.bfloat16)
    ints = sds((rows,), jnp.int32)
    compiled = _compiled(
        lambda q, k, v, t, n, s, i: paged_attention_kernel(
            q, k, v, t, n, layer=i, interpret=False,
            starts=s if bound else None),
        sds((rows, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
        sds((rows, columns), jnp.int32), ints, ints, sds((), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 2 * rows * heads * HEAD_DIM * 2


def test_a_decode_tick_lowers_without_the_tile_kernel(one_chip, monkeypatch):
    """A ``decode_fn`` has no prompt rows (``n_chunk`` 0): its lowered text
    names the row walk and not the tile kernel
    (``tests/test_paged_kernel.py`` pins that call's jaxpr to the one
    before the argument existed)."""
    text = _lowered_decode_tick(one_chip, monkeypatch).as_text()
    assert "paged_attention" in text
    assert "paged_attention_chunk" not in text


def _lowered_decode_tick(one_chip, monkeypatch, layers=2, rows=32):
    """A ``decode_fn``-shaped program lowered for the described chip:
    ``_PagedDecode`` at the 1.3B width, the benchmark's 32 rows over a
    2,721-page bf16 pool, the kernel path."""
    import importlib
    from paddle_tpu.inference.llm import _PagedDecode
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    from paddle_tpu.nn.layer import functional_call, split_state

    # the suite's interpret switch off: the program compiles its kernels
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    cfg = gpt_config("gpt3-1.3b", num_layers=layers, vocab_size=1024,
                     hidden_dropout=0.0, attention_dropout=0.0)
    assert (cfg.num_heads, cfg.head_dim) == (HEADS, HEAD_DIM)
    decode = _PagedDecode(GPTForCausalLM(cfg).eval(), "pallas")
    params, buffers = split_state(decode)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: sds(a.shape, jnp.bfloat16 if jnp.issubdtype(
                a.dtype, jnp.floating) else a.dtype), tree)

    def decode_fn(params, buffers, tokens, positions, tables, lens, kp, vp,
                  temps, nonces, key):
        return functional_call(decode, params, buffers, tokens, positions,
                               tables, lens, kp, vp, temps, nonces, key,
                               training=False)[0]

    pool = sds((layers, POOL_PAGES, PAGE, HEADS, HEAD_DIM), jnp.bfloat16)
    ints = sds((rows,), jnp.int32)
    return jax.jit(decode_fn, donate_argnums=(6, 7)).lower(
        described(params), described(buffers), ints, ints,
        sds((rows, MAX_LEN // PAGE), jnp.int32), ints, pool, pool,
        sds((rows,), jnp.float32), ints, sds((2,), jnp.uint32))


def test_decode_tick_keeps_no_copy_of_a_layers_pages(one_chip, monkeypatch):
    """The decode tick's temporaries stay far under ONE layer's K slice,
    which is what ``kv_layer`` used to copy out of the pool for every
    layer."""
    layers = 2
    compiled = _lowered_decode_tick(one_chip, monkeypatch, layers).compile()
    assert compiled.as_text().count("tpu_custom_call") >= layers
    layer_slice = POOL_PAGES * PAGE * HEADS * HEAD_DIM * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_slice // 8, (temp, layer_slice)


def test_engine_compiler_options_halve_a_ticks_async_prefetches(
        one_chip, monkeypatch):
    """What ``LLMEngine`` compiles its programs with on a TPU: the
    described v5e's compiler takes the options, and the decode tick then
    starts under half the async copies and slices (each is three events
    of a profiler trace: its start, its done and the async line's)."""
    from paddle_tpu.inference.llm import _TPU_COMPILER_OPTIONS
    lowered = _lowered_decode_tick(one_chip, monkeypatch, layers=4)

    def starts(compiled):
        text = compiled.as_text()
        return text.count(" copy-start(") + text.count(" slice-start(")

    plain = starts(lowered.compile())
    held = starts(lowered.compile(compiler_options=_TPU_COMPILER_OPTIONS))
    assert 0 < held <= plain // 2, (held, plain)


# the hybrid serving benchmark's state: 64 slots and a scratch row of
# 128 heads x 64 x 128 float32 a state-space layer
SLOTS, SSM_HEADS, SSM_HEAD_DIM, SSM_STATE = 64, 128, 64, 128
STATE_ROW_BYTES = SSM_HEADS * SSM_HEAD_DIM * SSM_STATE * 4


@pytest.mark.parametrize("head_block", [None, 16, 24],
                         ids=["hb_from_shapes", "hb16", "hb24_ragged"])
def test_ssd_step_kernel_compiles_for_v5e(one_chip, head_block):
    """The state step at the cell's shapes, a layer's whole
    ``[65, 128, 64, 128]`` array donated: aliased in place, no temporary."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(x, dt, a, b, c, d, state, live, first):
        return ssd_step_kernel(x, dt, a, b, c, d, state, live, first,
                               head_block=head_block, interpret=False)

    state = sds((SLOTS + 1, SSM_HEADS, SSM_HEAD_DIM, SSM_STATE))
    compiled = jax.jit(step, donate_argnums=(6,)).lower(
        sds((SLOTS, SSM_HEADS, SSM_HEAD_DIM), jnp.bfloat16),
        sds((SLOTS, SSM_HEADS)), sds((SSM_HEADS,)), sds((SLOTS, SSM_STATE)),
        sds((SLOTS, SSM_STATE)), sds((SSM_HEADS,)), state,
        sds((SLOTS,), jnp.bool_), sds((SLOTS,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == (SLOTS + 1) * STATE_ROW_BYTES
    assert mem.temp_size_in_bytes < STATE_ROW_BYTES // 4


@pytest.mark.parametrize("rows", [256, 64], ids=["chunk256", "chunk64"])
@pytest.mark.parametrize("head_block", [None, 8, 32],
                         ids=["hb_from_shapes", "hb8", "hb32"])
def test_ssd_chunk_kernel_compiles_for_v5e(one_chip, head_block, rows):
    """The chunk scan at the cell's shapes (256 prompt rows of bf16
    activations, up to 8 sequences) and at a shorter chunk, a layer's whole
    ``[65, 128, 64, 128]`` array donated: aliased in place, and beside the
    kernel only the transposed activations and a few small vectors."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scan(x, dt, a, b, c, d, state, seg, seg_rows, fresh):
        return ssd_chunk_kernel(x, dt, a, b, c, d, state, seg, seg_rows,
                                fresh, head_block=head_block,
                                interpret=False)

    bf16, i32 = jnp.bfloat16, jnp.int32
    state = sds((SLOTS + 1, SSM_HEADS, SSM_HEAD_DIM, SSM_STATE))
    compiled = jax.jit(scan, donate_argnums=(6,)).lower(
        sds((rows, SSM_HEADS, SSM_HEAD_DIM), bf16), sds((rows, SSM_HEADS)),
        sds((SSM_HEADS,)), sds((rows, SSM_STATE), bf16),
        sds((rows, SSM_STATE), bf16), sds((SSM_HEADS,)), state,
        sds((rows,), i32), sds((8,), i32), sds((8,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == (SLOTS + 1) * STATE_ROW_BYTES
    assert mem.temp_size_in_bytes < STATE_ROW_BYTES // 2


# the hybrid serving benchmark's routed experts: 36 held, hidden 4096,
# width 768; a decode tick's 64 rows x 10 experts and a mixed tick's 320
@pytest.mark.parametrize("rows,heads,dk,dv,channel", [
    (48, 32, 128, 128, True), (64, 30, 96, 256, False)],
    ids=["kimi_a_decay_a_channel", "olmo_one_decay_a_head"])
def test_kda_step_kernel_compiles_for_v5e(one_chip, rows, heads, dk, dv,
                                          channel):
    """The delta rule's step at both cells' shapes, a layer's whole
    ``[49, 32, 128, 128]`` / ``[65, 30, 96, 256]`` array donated: aliased in
    place, no temporary the size of a state row (the kernel's small
    operands are a few hundred KB)."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, k, v, log_a, b, state, live, first):
        return kda_step_kernel(q, k, v, log_a, b, state, live, first,
                               interpret=False)

    row_bytes = heads * dk * dv * 4
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        sds((rows, heads, dk)), sds((rows, heads, dk)),
        sds((rows, heads, dv)), sds((rows, heads, dk if channel else 1)),
        sds((rows, heads)), sds((rows + 1, heads, dk, dv)),
        sds((rows,), jnp.bool_), sds((rows,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == (rows + 1) * row_bytes
    assert mem.temp_size_in_bytes < row_bytes // 2


@pytest.mark.parametrize("pairs", [640, 3200], ids=["decode", "mixed"])
@pytest.mark.parametrize("weights", [(4096, 1536), (768, 4096)],
                         ids=["w_in", "w_out"])
def test_grouped_matmul_compiles_for_v5e(one_chip, weights, pairs):
    """The grouped product at the cell's shapes with the tiles it picks
    from them: a ring of full-``K`` weight tiles of megabytes in VMEM
    (over the compiler's default limit: the kernel raises it), nothing
    beside the kernel but the few small vectors of its plan."""
    k, n = weights

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compiled(
        lambda x, w, sizes: grouped_matmul(x, w, sizes, interpret=False),
        sds((pairs, k)), sds((36, k, n)), sds((36,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# a mixed tick's routed layer in the two cells that run one: rows, hidden,
# expert width, experts scored, experts held (top 10 in both)
@pytest.mark.parametrize("rows,hidden,width,experts,held", [
    (320, 4096, 768, 72, 36), (288, 3072, 1024, 256, 32)],
    ids=["hybrid-mixed", "window-mixed"])
def test_routed_layer_keeps_no_float32_copy_of_its_pairs(
        one_chip, monkeypatch, rows, hidden, width, experts, held):
    """``DroplessMoE.forward`` on the kernel path, compiled for the
    described chip at the cells' shapes: between the second grouped product
    and ``y`` stand ONE gather (a row's ten pairs' rows, bf16) and one
    fusion that converts, weighs and adds them: no operation of the program
    writes a float32 array of the ``T x k`` pairs' size and nothing
    scatters (before PR 38: a float32 ``[T*k, d]`` under a mask and a
    scatter-add of it by row, and a scatter-add of ones for the groups'
    sizes)."""
    import importlib
    import math
    import re
    from paddle_tpu.nn import DroplessMoE

    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    k = 10
    layer = DroplessMoE(8, 8, experts, k, (0, held))

    def forward(x, router, w_in, w_out):
        layer.router, layer.w_in, layer.w_out = router, w_in, w_out
        return layer(x, None, "pallas")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled(forward, sds((rows, hidden)),
                     sds((hidden, experts), jnp.float32),
                     sds((held, hidden, 2 * width)),
                     sds((held, width, hidden))).as_text()
    entry = text[text.index("ENTRY"):]
    made = [ln.split(" = ", 1)[1] for ln in entry.splitlines()
            if " = " in ln]
    assert sum(" custom-call(" in op and "grouped_matmul" in op
               for op in made) == 2
    assert not any("scatter" in op for op in made)
    pairs = rows * k
    wide = re.compile(r"^\(?f32\[(\d+(?:,\d+)*)\]")
    for op in made:
        m = wide.match(op)
        if m:
            size = math.prod(int(n) for n in m.group(1).split(","))
            assert size < pairs * hidden, op[:160]
    combine = [op for op in made if "moe/moe_combine/" in op
               and re.match(rf"\w+\[(\d+,)+{hidden}\]", op)]
    assert len(combine) == 2, combine          # the gather and the fusion


@pytest.fixture(scope="module")
def hybrid_programs(one_chip):
    """The engine's own ``decode`` and ``mixed`` programs of a hybrid model
    with the published state shape (three state-space layers and one that
    attends; the other widths small), compiled for the described chip as a
    TPU's engine builds them: kernels compiled, the decode rows' state
    through ``ssd_step_kernel``, the routed experts' groups through
    ``grouped_matmul``. ``{name: compiled}``."""
    import importlib
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import llm
    from paddle_tpu.models import (GraniteHybridConfig,
                                   GraniteHybridForCausalLM)

    mp = pytest.MonkeyPatch()
    mp.setattr(importlib.import_module("paddle_tpu.ops.flash_attention"),
               "INTERPRET", False)
    # the engine here lies on the CPU: the test, not an option, says what
    # the platform of a TPU's state would
    mp.setattr(llm, "_state_impl", lambda ssm_state, impls=None: "pallas")
    mp.setattr(llm, "_moe_impl", lambda net: "pallas")
    cfg = GraniteHybridConfig(
        vocab_size=1024, hidden_size=1024,
        layer_types=["mamba", "mamba", "mamba", "attention"],
        num_attention_heads=8, num_key_value_heads=8, intermediate_size=128,
        shared_intermediate_size=256, num_local_experts=8,
        num_experts_per_tok=2, max_position_embeddings=2048)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state) == (
        SSM_HEADS, SSM_HEAD_DIM, SSM_STATE)
    pt.seed(0)
    net = GraniteHybridForCausalLM(cfg)
    net.eval()
    chunk = 64
    eng = llm.LLMEngine(net, max_seqs=SLOTS, page_size=PAGE,
                        num_pages=SLOTS * 16 + 1, max_len=256,
                        prefill_chunk=chunk, attention_impl="pallas")
    try:
        assert (eng.state_impl, eng.moe_impl) == ("pallas", "pallas")

        def described(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=one_chip), tree)

        ints = np.zeros((SLOTS,), np.int32)
        decode = eng._decode_fn.lower(*described((
            eng._params, eng._buffers, eng._tokens_dev,
            eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
            eng._key) + eng._state_args())).compile()
        seg, seg_rows, _ = eng._chunk_segments((1, chunk))
        rows = np.zeros((1, chunk), np.int32)
        slots = np.zeros((1, SLOTS), np.int32)
        xs = {"tok": rows, "pos": rows, "lim": rows,
              "tbl": np.zeros((1, chunk, eng.pages_per_seq), np.int32),
              "fin": slots.astype(bool), "row": slots, "fpos": slots,
              "grant": slots, "seg": seg, "segrows": seg_rows}
        mixed = eng._mixed_fn.lower(*described((
            eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
            eng.block_tables, eng.temperatures, eng._nonces, eng._key)),
            1).compile()
    finally:
        eng.close()
        mp.undo()
    return {"decode": decode, "mixed": mixed}


@pytest.mark.parametrize("program,rows", [("decode", 8), ("mixed", 16)],
                         ids=["decode", "mixed"])
def test_hybrid_program_keeps_no_copy_of_a_layers_state(hybrid_programs,
                                                        program, rows):
    """Every state-space layer steps its state through the kernel (a mixed
    tick's chunk half through ``ssd_chunk`` too), the three layers' arrays
    are the program's own outputs (aliased), and the temporaries stay
    under a few state rows: 8 for a decode tick (it read 2.1, none of them
    state) and 16 for a mixed tick (it read 6.5; 44 while its chunk half
    gathered and scattered the state of the 8 sequences a chunk may carry
    around ``ssd_chunked``). A gather, a ``where`` or a scatter that XLA
    could not alias would add a layer's whole array, 65 rows, to either."""
    compiled = hybrid_programs[program]
    text = compiled.as_text()
    def calls(kernel):
        return [ln for ln in text.splitlines() if " custom-call(" in ln
                and "%" + kernel in ln.split(" = ")[0]]

    assert len(calls("ssd_step")) == 3
    assert len(calls("ssd_chunk")) == (3 if program == "mixed" else 0)
    assert reads_after_in_place_writes(text) == []
    # four layers, two grouped products each, and no ragged-dot left
    assert len(calls("grouped_matmul")) == 8 and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * (SLOTS + 1) * STATE_ROW_BYTES
    assert mem.temp_size_in_bytes < rows * STATE_ROW_BYTES, (
        mem.temp_size_in_bytes / STATE_ROW_BYTES)


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_looped_program_keeps_the_pool_in_place_through_its_passes(
        one_chip, monkeypatch, program):
    """The looped model's engine programs at Ouro-2.6B's widths (two layers,
    four passes, the cell's 8 slots and 128-row chunk): the passes are ONE
    ``while`` whose carry holds the pool, each layer's kernel call stands
    once in the text (not once a pass; a mixed tick's twice: the chunk's
    query tiles and the decode rows' row walk), both pools are the program's own
    outputs (aliased: the scatter at a traced cache layer and the kernel's
    read leave them where they are) and the temporaries stay under a
    twentieth of one pool (the decode tick read 2.9 MB). Unrolled or copied, a
    pass would add its kernel calls or a pool's 168 MB."""
    import importlib
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import llm
    from paddle_tpu.models import OuroConfig, OuroForCausalLM

    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    layers, slots, chunk = 2, 8, 128
    cfg = OuroConfig(num_hidden_layers=layers, vocab_size=1024)
    assert (cfg.num_heads, cfg.head_dim, cfg.total_ut_steps) == (
        HEADS, HEAD_DIM, 4)
    pt.seed(0)
    net = OuroForCausalLM(cfg).astype("bfloat16")
    net.eval()
    eng = llm.LLMEngine(net, max_seqs=slots, page_size=PAGE, num_pages=321,
                        max_len=640, prefill_chunk=chunk, kv_dtype="bf16",
                        attention_impl="pallas")
    try:
        def described(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=one_chip), tree)

        ints = np.zeros((slots,), np.int32)
        if program == "decode":
            lowered = eng._decode_fn.lower(*described((
                eng._params, eng._buffers, eng._tokens_dev,
                eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
                eng._key)))
        else:
            rows = np.zeros((1, chunk), np.int32)
            per_slot = np.zeros((1, slots), np.int32)
            xs = {"tok": rows, "pos": rows, "lim": rows,
                  "tbl": np.zeros((1, chunk, eng.pages_per_seq), np.int32),
                  "fin": per_slot.astype(bool), "row": per_slot,
                  "fpos": per_slot, "grant": per_slot}
            lowered = eng._mixed_fn.lower(*described((
                eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
                eng.block_tables, eng.temperatures, eng._nonces,
                eng._key)), 1)
        pool = eng.k_pages.nbytes
        assert pool == 4 * layers * 321 * PAGE * HEADS * HEAD_DIM * 2
    finally:
        eng.close()
    compiled = lowered.compile()
    text = compiled.as_text()
    # a mixed tick's layer calls the kernel twice: its chunk's rows through
    # query tiles, its decode rows through the row walk
    per_layer = 2 if program == "mixed" else 1
    assert text.count("tpu_custom_call") == per_layer * layers
    assert ("paged_attention_chunk" in text) == (program == "mixed")
    assert " while(" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool
    assert mem.temp_size_in_bytes < pool // 20, (
        mem.temp_size_in_bytes, pool)


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_windowed_program_keeps_both_groups_pools_in_place(
        one_chip, monkeypatch, program):
    """The window / full attention model's engine programs at Laguna-S-2.1's
    widths (hidden 3072, 8 K/V heads of 128), two layers of each kind (48
    heads on a full layer, 72 on a sliding one: 6 and 9 a K/V head), four of
    the 256 experts held, the cell's 32 slots, 256-row chunk and 576-column
    tables: one kernel call a layer (two in a mixed tick: the chunk's rows
    through query tiles, the decode rows through the row walk), the sliding
    layers' with a lower bound a row, every routed layer's two
    grouped products through the kernel, BOTH groups' pools the program's
    own outputs (aliased) and the temporaries under a quarter of the pools (a copy of one group's K or V
    would be a quarter; the mixed tick read 28 MB of 168). The
    scalar-prefetched tables of a mixed tick, [288, 576] int32 a call, must
    fit SMEM: a compile that passes says they do."""
    import importlib
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import llm
    from paddle_tpu.models import LagunaConfig, LagunaForCausalLM

    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    monkeypatch.setattr(llm, "_moe_impl", lambda net: "pallas")
    slots, chunk, max_len = 32, 256, 9216
    cfg = LagunaConfig(
        num_hidden_layers=4, vocab_size=1024, experts_held=(0, 4),
        layer_types=["full_attention", "sliding_attention",
                     "sliding_attention", "full_attention"],
        num_attention_heads_per_layer=[48, 72, 72, 48])
    assert (cfg.hidden_size, cfg.num_kv_heads, cfg.head_dim,
            cfg.sliding_window, cfg.num_experts) == (3072, 8, 128, 512, 256)
    pt.seed(0)
    net = LagunaForCausalLM(cfg).astype("bfloat16")
    net.eval()
    eng = llm.LLMEngine(net, max_seqs=slots, page_size=PAGE, num_pages=1281,
                        max_len=max_len, prefill_chunk=chunk,
                        kv_dtype="bf16", attention_impl="pallas")
    try:
        def described(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=one_chip), tree)

        full, window = eng._pool.groups
        assert (full.num_pages, window.num_pages, window.ring) == (
            1281, 1281, 49)
        assert eng.pages_per_seq == 576
        ints = np.zeros((slots,), np.int32)
        tables = eng._pool.device_tables()
        if program == "decode":
            lowered = eng._decode_fn.lower(*described((
                eng._params, eng._buffers, eng._tokens_dev,
                eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
                eng._key)))
        else:
            rows = np.zeros((1, chunk), np.int32)
            per_slot = np.zeros((1, slots), np.int32)
            xs = {"tok": rows, "pos": rows, "lim": rows,
                  "tbl": eng._pool.row_tables(np.full((1, chunk), -1)),
                  "fin": per_slot.astype(bool), "row": per_slot,
                  "fpos": per_slot, "grant": per_slot}
            lowered = eng._mixed_fn.lower(*described((
                eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
                tables, eng.temperatures, eng._nonces, eng._key)), 1)
        pools = sum(a.nbytes for a in eng.k_pages)
        assert pools == 4 * 1281 * PAGE * 8 * 128 * 2
    finally:
        eng.close()
    compiled = lowered.compile()
    text = compiled.as_text()

    def calls(kernel):
        return [ln for ln in text.splitlines() if " custom-call(" in ln
                and "%" + kernel in ln.split(" = ")[0]]

    # a layer's row walk, and in a mixed tick its chunk's query tiles too
    assert len(calls("paged_attention.")) == 4
    assert len(calls("paged_attention_chunk")) \
        == (4 if program == "mixed" else 0)
    assert len(calls("grouped_matmul")) == 6 and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pools
    assert mem.temp_size_in_bytes < pools // 4, (
        mem.temp_size_in_bytes, pools)


# -- a latent cache group and a delta-rule state (Kimi Linear's widths) -------

KDA_CELL = dict(slots=48, chunk=256, max_len=7680, width=640, lora=512,
                heads=32, mla_layers=7, pages=48 * 480 + 1)


@pytest.mark.parametrize("rows,n_chunk", [(48, 0), (304, 256)],
                         ids=["decode", "mixed"])
def test_latent_attention_compiles_for_v5e_and_copies_a_page_once(
        one_chip, rows, n_chunk):
    """The row walk and the query tiles over ``reason_closed_kda``'s latent
    pool (7 layers x 23,041 pages of 16 rows of 640 bf16 values), 32 heads
    a row, a 480-column table: no copy of the pool beside the kernel, and
    ONE copy a page in the kernel's text where a K/V pool's has two (the
    same rows serve the scores and the values)."""
    c = KDA_CELL

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((c["mla_layers"], c["pages"], PAGE, c["width"]),
               jnp.bfloat16)
    q = sds((rows, c["heads"], c["width"]), jnp.bfloat16)
    tables = sds((rows, c["max_len"] // PAGE), jnp.int32)
    lens, layer = sds((rows,), jnp.int32), sds((), jnp.int32)

    def latent(q, p, t, n, i):
        return paged_attention_kernel(q, p, None, t, n, layer=i,
                                      interpret=False, n_chunk=n_chunk,
                                      value_dim=c["lora"])

    compiled = _compiled(latent, q, pool, tables, lens, layer)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 7 * c["pages"] * PAGE * 640 * 2
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes >= rows * 32 * 512 * 2

    # in the kernels' own text: one page buffer, one copy a page
    def copies(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count("dma_start")

    kv = sds((c["mla_layers"], 64, PAGE, 1, 128), jnp.bfloat16)
    q_kv = sds((rows, c["heads"], 128), jnp.bfloat16)
    both = copies(lambda q, k, v, t, n, i: paged_attention_kernel(
        q, k, v, t, n, layer=i, interpret=False, n_chunk=n_chunk),
        q_kv, kv, kv, tables, lens, layer)
    once = copies(latent, q, pool, tables, lens, layer)
    assert once > 0 and 2 * once == both


def test_a_latent_row_of_576_is_refused_by_the_compiler(one_chip):
    """Why the row is stored 640 wide: the pool's last axis is tiled to
    whole lanes of 128 in HBM whatever its logical width, and Mosaic
    refuses a page slice that is not."""
    c = KDA_CELL

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda q, p, t, n, i: paged_attention_kernel(
            q, p, None, t, n, layer=i, interpret=False,
            value_dim=c["lora"])).lower(
            sds((48, 32, 576), jnp.bfloat16),
            sds((7, 257, PAGE, 576), jnp.bfloat16),
            sds((48, 16), jnp.int32), sds((48,), jnp.int32),
            sds((), jnp.int32)).compile()


@pytest.fixture(scope="module")
def kda_programs(one_chip):
    """``reason_closed_kda``'s two engine programs at the published widths
    (hidden 2304; KDA 32 heads of 128 with a conv of 4; MLA 32 heads of
    128 + 64 / 128 over a latent of 512; 16 of 256 experts of 1024 held),
    the cell's 48 slots, 256-row chunk and 480-column table, at a depth of
    FIVE (the dense KDA layer, two routed KDA layers, a routed MLA layer,
    a routed KDA layer) over a pool of 257 pages: what grows with depth
    and with the pool is arguments, reckoned from the shapes below.
    ``{name: (compiled, state bytes, pool bytes)}``."""
    import importlib
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import llm
    from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM

    mp = pytest.MonkeyPatch()
    mp.setattr(importlib.import_module("paddle_tpu.ops.flash_attention"),
               "INTERPRET", False)
    # the engine here lies on the CPU: the test, not an option, hands it
    # what the spec and a TPU give, the step's kernel
    mp.setattr(llm, "_state_impl", lambda ssm_state, impls=None: "pallas")
    mp.setattr(llm, "_moe_impl", lambda net: "pallas")
    c = KDA_CELL
    cfg = KimiLinearConfig(num_hidden_layers=5, vocab_size=1024,
                           experts_held=(0, 16))
    assert (cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim,
            cfg.latent_width, cfg.kv_lora_rank, cfg.num_experts) == (
        2304, 32, 128, 640, 512, 256)
    assert cfg.layer_kinds == ("kda", "kda", "kda", "mla", "kda")
    pt.seed(0)
    net = KimiLinearForCausalLM(cfg).astype("bfloat16")
    net.eval()
    eng = llm.LLMEngine(net, max_seqs=c["slots"], page_size=PAGE,
                        num_pages=257, max_len=c["max_len"],
                        prefill_chunk=c["chunk"], kv_dtype="bf16",
                        attention_impl="pallas")
    try:
        assert (eng.state_impl, eng.moe_impl) == ("pallas", "pallas")
        # the chunk form is the gathered one under either value
        assert not eng._chunk_in_place

        def described(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=one_chip), tree)

        slots, chunk = c["slots"], c["chunk"]
        ints = np.zeros((slots,), np.int32)
        tables = eng._pool.device_tables()
        assert eng.pages_per_seq == 480 and eng.v_pages == (None,)
        lowered = {"decode": eng._decode_fn.lower(*described((
            eng._params, eng._buffers, eng._tokens_dev,
            eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
            eng._key) + eng._state_args()))}
        seg, seg_rows, _ = eng._chunk_segments((1, chunk))
        rows = np.zeros((1, chunk), np.int32)
        per_slot = np.zeros((1, slots), np.int32)
        xs = {"tok": rows, "pos": rows, "lim": rows,
              "tbl": eng._pool.row_tables(np.full((1, chunk), -1)),
              "fin": per_slot.astype(bool), "row": per_slot,
              "fpos": per_slot, "grant": per_slot, "seg": seg,
              "segrows": seg_rows}
        lowered["mixed"] = eng._mixed_fn.lower(*described((
            eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
            tables, eng.temperatures, eng._nonces, eng._key)), 1)
        state = sum(a.nbytes for a in eng.conv_state + eng.ssm_state)
        pool = eng.k_pages[0].nbytes
        assert state == 49 * 4 * (2_097_152 + 73_728)
        assert pool == 257 * PAGE * 640 * 2
    finally:
        eng.close()
        mp.undo()
    return {name: (low.compile(), state, pool)
            for name, low in lowered.items()}


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_kda_program_fits_the_chip_at_full_depth_and_keeps_state_and_pool_in_place(
        kda_programs, program):
    """One kernel call an MLA layer (two in a mixed tick: the chunk's rows
    through query tiles, the decode rows through the row walk), every
    routed layer's two grouped products through the kernel, the state rows
    and the latent pool the program's own outputs (aliased), and the
    temporaries (which do not grow with depth: the layers run one after
    another) small enough that the cell's arguments at ALL 27 layers,
    8.59 GB of weights + 2.13 GB of state + 3.30 GB of pool, stay under
    0.90 x ``bytes_limit`` beside them."""
    compiled, state, pool = kda_programs[program]
    text = compiled.as_text()

    def calls(kernel):
        return [ln for ln in text.splitlines() if " custom-call(" in ln
                and "%" + kernel in ln.split(" = ")[0]]

    assert len(calls("paged_attention.")) == 1
    assert len(calls("paged_attention_chunk")) == (program == "mixed")
    assert len(calls("grouped_matmul")) == 8 and "ragged-dot" not in text
    # every KDA layer's decode rows step their state through the kernel,
    # in the decode program and in the decode half of the mixed one
    assert len(calls("kda_step")) == 4
    assert reads_after_in_place_writes(text) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state + pool
    # a copy of ONE layer's state rows would be 103 MB; the chunk form's
    # largest temporaries are two [16, 256, 32, 128] float32 arrays
    budget = {"decode": 64 << 20, "mixed": 400 << 20}[program]
    assert mem.temp_size_in_bytes < budget, mem.temp_size_in_bytes
    full_depth = 8.592e9 + 49 * 43_417_600 + 23_041 * 143_360
    assert full_depth + mem.temp_size_in_bytes < 0.90 * 16_909_336_064


def test_chunk_forms_inverse_takes_its_blocks_by_slices_on_the_chip(one_chip):
    """One KDA layer's ``kda_chunk_gathered`` at ``reason_closed_kda``'s
    shapes (256 packed rows of 32 heads of 128, float32, 8 sequences a
    chunk, a layer's 48 + 1 state rows), compiled for the described v5e.
    The blocks the inverse's doubling reads are cut out of ``L`` by slices:
    no operation comes from the einsum with a repeated index that stood
    there (``...iaib->...iab``: an ``iota``, a compare, a select against
    zeros and a sum over all of ``L``, at each of eight levels), nothing of
    a level's masked shape ``[32, n, 2s, n, 2s]`` is made, and the program
    has no more fusions than the parent's text of the same function: 139
    there (PR 42's tree, this compiler, counted as below), 119 here; a
    fusion costs microseconds on the chip whatever it moves."""
    import re
    from paddle_tpu.ops.kda import kda_chunk_gathered

    c = KDA_CELL
    t, h, d, g = c["chunk"], c["heads"], 128, 8

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(kda_chunk_gathered).lower(
        sds((t, h, d)), sds((t, h, d)), sds((t, h, d)), sds((t, h, d)),
        sds((t, h)), sds((c["slots"] + 1, h, d, d)), sds((t,), jnp.int32),
        sds((g,), jnp.int32), sds((g,), jnp.bool_)).compile().as_text()
    assert "iaib" not in text
    assert "kda_chunk_gathered)/inverse/" in text        # the scope's name
    s = 1
    while s < t:
        n = t // (2 * s)
        assert f"f32[{h},{n},{2 * s},{n},{2 * s}]" not in text, (n, s)
        s *= 2
    entry = text[text.index("ENTRY"):]
    fusions = [ln for ln in entry.splitlines()
               if re.search(r" = .* fusion\(", ln)]
    # (the lower limit: the pattern still finds the program's fusions)
    assert 80 < len(fusions) <= 139, len(fusions)


# -- a sink, keys of 192 beside values of 128, a window under the chunk -------

@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_sink_program_compiles_at_mimos_widths_and_keeps_its_pools_in_place(
        one_chip, monkeypatch, program):
    """The sink model's engine programs at MiMo-V2-Flash's widths (hidden
    4096, 64 query heads of 192 over 4 K/V heads on a full layer and 8 on a
    sliding one, values of 128, K pages stored 256 wide), a full layer and
    two sliding ones, two of the 256 experts held, the cell's 48 slots,
    256-row chunk over window 128 and 608-column tables: one kernel call a
    layer (two in a mixed tick: the chunk's rows through query tiles, the
    decode rows through the row walk, both on the MXU fold: 16 and 8 query
    heads a K/V head), the sliding layers' with a lower bound a row and the
    sink as an operand, every routed layer's two grouped products through
    the kernel, BOTH groups' K AND V pools the program's own outputs
    (aliased) and the temporaries under a quarter of the pools. What Mosaic
    refuses of these shapes (a 4-row head axis, a 256-wide K buffer beside a
    128-wide V buffer, the tile kernel's VMEM) it refuses here."""
    import importlib
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import llm
    from paddle_tpu.models import MiMoV2Config, MiMoV2ForCausalLM

    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    monkeypatch.setattr(llm, "_moe_impl", lambda net: "pallas")
    slots, chunk, max_len = 48, 256, 9728
    cfg = MiMoV2Config(num_hidden_layers=3, vocab_size=1024,
                       experts_held=(0, 2), hybrid_layer_pattern=[0, 1, 1],
                       moe_layer_freq=[0, 1, 1])
    assert (cfg.hidden_size, cfg.sliding_window, cfg.n_routed_experts) == (
        4096, 128, 256)
    pt.seed(0)
    net = MiMoV2ForCausalLM(cfg).astype("bfloat16")
    net.eval()
    eng = llm.LLMEngine(net, max_seqs=slots, page_size=PAGE, num_pages=1825,
                        max_len=max_len, prefill_chunk=chunk,
                        kv_dtype="bf16", attention_impl="pallas")
    try:
        def described(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=one_chip), tree)

        full, window = eng._pool.groups
        assert (full.num_pages, window.num_pages, window.ring) == (
            1825, 48 * 25 + 1, 25)
        assert eng.pages_per_seq == 608
        assert full.k_pages.shape == (1, 1825, PAGE, 4, 256)
        assert full.v_pages.shape == (1, 1825, PAGE, 4, 128)
        assert window.k_pages.shape == (2, 1201, PAGE, 8, 256)
        assert window.v_pages.shape == (2, 1201, PAGE, 8, 128)
        ints = np.zeros((slots,), np.int32)
        tables = eng._pool.device_tables()
        if program == "decode":
            lowered = eng._decode_fn.lower(*described((
                eng._params, eng._buffers, eng._tokens_dev,
                eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
                eng._key)))
        else:
            rows = np.zeros((1, chunk), np.int32)
            per_slot = np.zeros((1, slots), np.int32)
            xs = {"tok": rows, "pos": rows, "lim": rows,
                  "tbl": eng._pool.row_tables(np.full((1, chunk), -1)),
                  "fin": per_slot.astype(bool), "row": per_slot,
                  "fpos": per_slot, "grant": per_slot}
            lowered = eng._mixed_fn.lower(*described((
                eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
                tables, eng.temperatures, eng._nonces, eng._key)), 1)
        pools = sum(a.nbytes for a in eng.k_pages + eng.v_pages)
        assert pools == (1825 * 4 + 2 * 1201 * 8) * PAGE * (256 + 128) * 2
    finally:
        eng.close()
    compiled = lowered.compile()
    text = compiled.as_text()

    def calls(kernel):
        return [ln for ln in text.splitlines() if " custom-call(" in ln
                and "%" + kernel in ln.split(" = ")[0]]

    assert len(calls("paged_attention.")) == 3
    assert len(calls("paged_attention_chunk")) \
        == (3 if program == "mixed" else 0)
    assert len(calls("grouped_matmul")) == 4 and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    print("MIMO", program, "temp", mem.temp_size_in_bytes, "alias",
          mem.alias_size_in_bytes, "args", mem.argument_size_in_bytes,
          "pools", pools)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools // 4, (
        mem.temp_size_in_bytes, pools)


# -- a delta rule with one decay a head, thirty K/V heads, the whole vocabulary

GDN_CELL = dict(slots=64, chunk=256, max_len=3584, pages=64 * 224 + 1)


@pytest.fixture(scope="module")
def gdn_programs(one_chip):
    """``reason_closed_gdn``'s two engine programs at the published widths
    (hidden 3840; the rule's 30 heads of 96 x 192 with a conv of 4, its
    state stored ``[30, 96, 256]``; 30 heads of 128 over 30 K/V heads in
    pages of 32 stored heads; SwiGLU 11008), the cell's 64 slots, 256-row
    chunk and 224-column table, at a depth of FOUR (one period: three
    ``linear_attention`` layers and a ``full_attention`` one) and a
    vocabulary of 1,024 over a pool of 257 pages: what grows with depth,
    with the vocabulary and with the pool is arguments and one array of
    logits, reckoned from the shapes below. ``{name: (compiled, state
    bytes, pool bytes)}``."""
    import importlib
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference import llm
    from paddle_tpu.models import OlmoHybridConfig, OlmoHybridForCausalLM

    mp = pytest.MonkeyPatch()
    mp.setattr(importlib.import_module("paddle_tpu.ops.flash_attention"),
               "INTERPRET", False)
    # the engine here lies on the CPU: the test, not an option, hands it
    # what the spec and a TPU give, the step's kernel
    mp.setattr(llm, "_state_impl", lambda ssm_state, impls=None: "pallas")
    c = GDN_CELL
    cfg = OlmoHybridConfig(num_layers=4, vocab_size=1024)
    assert (cfg.hidden_size, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.value_width, cfg.num_key_value_heads, cfg.head_dim) == (
        3840, 30, 96, 192, 256, 30, 128)
    assert cfg.layer_kinds == ("linear_attention",) * 3 + (
        "full_attention",)
    pt.seed(0)
    net = OlmoHybridForCausalLM(cfg).astype("bfloat16")
    net.eval()
    eng = llm.LLMEngine(net, max_seqs=c["slots"], page_size=PAGE,
                        num_pages=257, max_len=c["max_len"],
                        prefill_chunk=c["chunk"], kv_dtype="bf16",
                        attention_impl="pallas")
    try:
        assert eng.state_impl == "pallas" and not eng._chunk_in_place

        def described(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=one_chip), tree)

        slots, chunk = c["slots"], c["chunk"]
        ints = np.zeros((slots,), np.int32)
        tables = eng._pool.device_tables()
        (full,) = eng._pool.groups
        assert eng.pages_per_seq == 224
        # thirty heads in pages of 32 (the model's ``kv_cache_spec()``
        # names the 32): what HBM stores, counted as stored
        assert (cfg.num_key_value_heads, cfg.stored_kv_heads) == (30, 32)
        assert full.k_pages.shape == full.v_pages.shape == (
            1, 257, PAGE, 32, 128)
        assert full.page_bytes == 2 * PAGE * 32 * 128 * 2
        lowered = {"decode": eng._decode_fn.lower(*described((
            eng._params, eng._buffers, eng._tokens_dev,
            eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
            eng._key) + eng._state_args()))}
        seg, seg_rows, _ = eng._chunk_segments((1, chunk))
        rows = np.zeros((1, chunk), np.int32)
        per_slot = np.zeros((1, slots), np.int32)
        xs = {"tok": rows, "pos": rows, "lim": rows,
              "tbl": eng._pool.row_tables(np.full((1, chunk), -1)),
              "fin": per_slot.astype(bool), "row": per_slot,
              "fpos": per_slot, "grant": per_slot, "seg": seg,
              "segrows": seg_rows}
        lowered["mixed"] = eng._mixed_fn.lower(*described((
            eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
            tables, eng.temperatures, eng._nonces, eng._key)), 1)
        state = sum(a.nbytes for a in eng.conv_state + eng.ssm_state)
        pool = full.k_pages.nbytes + full.v_pages.nbytes
        # a row as STORED: [30, 96, 256] float32 + a [3, 11520] bf16 tail
        assert state == 65 * 3 * (2_949_120 + 69_120)
    finally:
        eng.close()
        mp.undo()
    return {name: (low.compile(), state, pool)
            for name, low in lowered.items()}


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_gdn_program_fits_the_chip_at_eight_layers_and_keeps_state_and_pool_in_place(
        gdn_programs, program):
    """One kernel call a ``full_attention`` layer (two in a mixed tick: the
    chunk's rows through query tiles, the decode rows through the row walk;
    Mosaic takes a 32-row head axis where it refuses 30), the state rows and
    the K and V pools the program's own outputs (aliased), and the
    temporaries (which do not grow with depth: the layers run one after
    another) small enough that the cell's arguments at ALL 8 layers and the
    whole vocabulary, 4.87 GB of weights + 1.18 GB of state + 7.52 GB of
    pool, and a tick's logits over 100,352 ids stay under 0.90 x
    ``bytes_limit`` beside them."""
    compiled, state, pool = gdn_programs[program]
    text = compiled.as_text()

    def calls(kernel):
        return [ln for ln in text.splitlines() if " custom-call(" in ln
                and "%" + kernel in ln.split(" = ")[0]]

    assert len(calls("paged_attention.")) == 1
    assert len(calls("paged_attention_chunk")) == (program == "mixed")
    # every ``linear_attention`` layer's decode rows step their state
    # through the kernel, in both programs
    assert len(calls("kda_step")) == 3
    assert reads_after_in_place_writes(text) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state + pool
    # a copy of ONE layer's state rows would be 196 MB
    budget = {"decode": 150 << 20, "mixed": 600 << 20}[program]
    assert mem.temp_size_in_bytes < budget, mem.temp_size_in_bytes
    c = GDN_CELL
    logits = 2 * c["slots"] * 100_352 * 4         # float32, and a copy
    eight_layers = 2_435_748_072 * 2 + 65 * 6 * (2_949_120 + 69_120) \
        + c["pages"] * 2 * 2 * PAGE * 32 * 128 * 2
    assert eight_layers + logits + mem.temp_size_in_bytes \
        < 0.90 * 16_909_336_064


@pytest.mark.parametrize("n_chunk", [0, 256], ids=["row-walk", "tiles"])
def test_a_head_axis_of_thirty_is_refused_by_the_compiler(one_chip,
                                                          monkeypatch,
                                                          n_chunk):
    """Why ``models/olmo_hybrid.py`` names 32 stored heads for its thirty
    (``OlmoHybridConfig.stored_kv_heads``): a page's
    heads lie on the sublanes, the array in HBM is tiled to whole tiles
    whatever its logical shape (the memref Mosaic is handed is 32 deep), and
    a page slice of 30 rows is refused, in the row walk and in the query
    tiles alike; the same call over pages of 32 compiles (the programs
    above)."""
    import importlib
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    rows = n_chunk + GDN_CELL["slots"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((2, 257, PAGE, 30, HEAD_DIM), jnp.bfloat16)
    with pytest.raises(Exception, match=r"aligned to tiling \(8\), but is "
                                        r"30"):
        jax.jit(lambda q, k, v, t, n, i: ragged_paged_attention(
            q, k, v, t, n, impl="pallas", layer=i, n_chunk=n_chunk)).lower(
            sds((rows, 30, HEAD_DIM), jnp.bfloat16), pool, pool,
            sds((rows, 224), jnp.int32), sds((rows,), jnp.int32),
            sds((), jnp.int32)).compile()
