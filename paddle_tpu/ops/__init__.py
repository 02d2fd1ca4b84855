"""paddle_tpu.ops — custom TPU kernels (Pallas/Mosaic).

The reference implements its fused hot-path ops as hand-written CUDA
(reference: paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h, fused_multi_transformer_op.cu). The TPU-native equivalents
live here as Pallas kernels compiled by Mosaic. They compile for the
TPU or raise; the CPU test suite runs them through the Pallas
interpreter by one switch of its own (tests/conftest.py).
"""

from .flash_attention import flash_attention  # noqa
from .ring_attention import ring_attention  # noqa: F401
from .fused_xent import fused_linear_cross_entropy  # noqa
from .paged_attention import (PagedKVCache, QuantizedKV,  # noqa
                              paged_attention,  # noqa
                              paged_attention_ragged,  # noqa
                              ragged_paged_attention,  # noqa
                              ragged_paged_attention_reference)  # noqa
from .rotary import apply_rotary_pos_emb, rope_tables  # noqa
