"""Device scopes (ISSUE 24, D): the ``jax.named_scope`` names of the model,
the engine programs and the train step reach the lowered programs' operation
metadata (what a device trace carries as ``tf_op``), and the pallas kernels
carry their ``name=``. Metadata only: the checks read lowered text."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.gpt import (GPTForCausalLM,
                                   GPTFusedPretrainingCriterion, gpt_config)

MODEL = ("embed", "ln", "attn", "mlp", "lm_head")
ENGINE = ("kv_write", "kv_layer", "kv_gather", "attn_scores", "sample")


def _tiny(**kw):
    pt.seed(0)
    return GPTForCausalLM(gpt_config(
        "gpt2-small", num_layers=2, hidden_size=64, num_heads=4,
        vocab_size=97, max_position_embeddings=96, hidden_dropout=0.0,
        attention_dropout=0.0, **kw))


def _abstract(args):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)


class _Spy:
    """Stands in for a jitted program: keeps the abstract arguments of its
    last call, so the test can lower exactly what the program runs."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        self.args = _abstract(args)
        return self.fn(*args)

    def text(self):
        return self.fn.lower(*self.args).as_text(debug_info=True)


def _scopes_in(text, names):
    # a scope shows as a path component, bare (".../kv_gather/gather") or
    # inside the transform it was traced under ("jvp(attn)/dot_general")
    return {n for n in names if re.search(rf"[/(]{n}[/)]", text)}


@pytest.fixture(scope="module")
def engine_texts():
    from paddle_tpu.inference.llm import LLMEngine
    rng = np.random.RandomState(0)
    with LLMEngine(_tiny(), max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8) as eng:
        mixed, decode = _Spy(eng._mixed_fn), _Spy(eng._decode_fn)
        eng._mixed_fn, eng._decode_fn = mixed, decode
        eng.generate([rng.randint(0, 97, 11).tolist()], max_new_tokens=6)
        assert mixed.args is not None and decode.args is not None
        return {"mixed_fn": mixed.text(), "decode_fn": decode.text()}


@pytest.mark.parametrize("program", ["mixed_fn", "decode_fn"])
def test_engine_programs_carry_model_and_engine_scopes(engine_texts,
                                                       program):
    text = engine_texts[program]
    assert _scopes_in(text, MODEL + ENGINE) == set(MODEL + ENGINE)


def test_train_step_carries_model_loss_and_optimizer_scopes():
    net = _tiny(fused_loss=True)
    m = pt.Model(net)
    m.prepare(optimizer=pt.optimizer.AdamW(learning_rate=1e-3,
                                           parameters=net),
              loss=GPTFusedPretrainingCriterion())
    ids = np.random.RandomState(0).randint(0, 97, (2, 16)).astype(np.int32)
    m.train_batch([ids], [ids])
    spy = _Spy(m._train_step_fn)
    m._train_step_fn = spy
    m.train_batch([ids], [ids])
    text = spy.text()
    want = ("embed", "ln", "attn", "mlp", "loss", "fused_xent", "optimizer")
    assert _scopes_in(text, want) == set(want)
    # the backward pass keeps the scope inside its transform's name
    assert "transpose(jvp(mlp))" in text or "jvp(mlp)" in text


def test_pallas_kernels_carry_their_names():
    from paddle_tpu.ops.flash_attention import flash_attention
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in jaxpr, name
