"""Long-context training with sequence parallelism (context parallel).

A LLaMA-style model training on 16k-token sequences that no single
device's attention could hold densely: ``GPTConfig.sequence_parallel``
routes attention through ring attention over the mesh's ``sp`` axis
(K/V chunks rotate the ICI ring; exact numerics), and
``ring_chunk_size`` streams each block's K/V in tiles so per-device
attention memory is O(s * chunk / sp) rather than O((s/sp)^2).
``scan_layers`` keeps the compile O(1) in depth with structural remat.

Run (CPU demo: 8 virtual devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_ring.py
On a real TPU slice, drop the env var — the mesh picks up the chips.
"""

import os
import sys

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

# pass --tpu to run on an attached TPU slice; the default pins the CPU
# demo WITHOUT probing the backend (a TPU belongs to one process at a
# time, and the demo has no need of it)
ON_TPU = "--tpu" in sys.argv
if not ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import parallel
from paddle_tpu.models.gpt import (GPTForCausalLM,
                                   GPTPretrainingCriterion, llama_config)


def main():
    # 16k tokens on a real slice; the CPU demo default stays small
    # enough to compile+run in minutes on a laptop core
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    seq = int(args[0]) if args else (16384 if ON_TPU else 4096)
    sp, dp = 4, 2

    cfg = llama_config(hidden_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, vocab_size=512,
                       max_position_embeddings=seq, use_flash=False,
                       scan_layers=True, remat=True,
                       sequence_parallel=True, ring_chunk_size=min(512, seq // sp))
    mesh = parallel.init_mesh(sp=sp, dp=dp)

    paddle.seed(0)
    net = GPTForCausalLM(cfg)
    model = paddle.Model(net)
    model.prepare(optimizer=paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=net, weight_decay=0.1),
        loss=GPTPretrainingCriterion())
    parallel.distributed_model(model, mesh=mesh)

    rng = np.random.RandomState(0)
    for step in range(3):
        ids = rng.randint(0, cfg.vocab_size, (2 * dp, seq))
        logs = model.train_batch([ids], [ids])
        print(f"step {step}: loss {logs['loss']:.4f} "
              f"({2 * dp} x {seq} tokens over sp={sp} ring)")


if __name__ == "__main__":
    main()
