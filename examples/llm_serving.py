"""Continuous-batching LLM serving demo.

Builds a small GPT, serves it through ``inference.LLMEngine`` (paged
KV cache, token-granularity admission, on-device sampling) behind the
HTTP front, and fires concurrent clients at it — the decode-era analog
of `serve_native.py`'s static-artifact serving.

Run: python examples/llm_serving.py  (CPU or TPU; first compile is
the slow part on TPU — subsequent requests share the jitted step)
"""

import json
import threading
import time
from urllib.request import Request, urlopen

import numpy as np

import paddle_tpu as pt
from paddle_tpu.inference import LLMEngine, serve_llm
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import server as debug
from paddle_tpu.observability import tracing


def main():
    pt.seed(0)
    # request-scoped tracing + the live debug surface: scrape
    # /metrics, inspect /statusz occupancy, read /tracez span trees
    tracing.enable()
    cfg = gpt_config("gpt2-small", num_layers=4, hidden_size=256,
                     num_heads=4, vocab_size=1000,
                     max_position_embeddings=256,
                     hidden_dropout=0.0, attention_dropout=0.0)
    net = GPTForCausalLM(cfg)

    # decode_ticks_per_dispatch=8: the device-resident decode loop —
    # 8 decode ticks per XLA dispatch (sampling/EOS/page writes on
    # device), ~2x decode tokens/sec at small batch on CPU (PERF.md
    # "serving dispatch overhead"); watch llm_host_dispatches_total
    # vs llm_decode_ticks on /metrics to see the fusion.
    # Prefill chunks ride INSIDE the slab as one ragged batch with
    # the decode rows (llm_mixed_slabs_total).
    # kv_dtype="int8": quantized KV pages + per-token scales — ~2x
    # page capacity at fixed HBM (the /memz kv_pool rows show the
    # int8-page / scale_table split; PERF.md "Ragged mixed tick +
    # int8 KV" documents the greedy-parity tolerance).
    with LLMEngine(net, max_seqs=8, page_size=16, num_pages=256,
                   prefill_chunk=32, decode_ticks_per_dispatch=8,
                   kv_dtype="int8") as engine:
        srv = serve_llm(engine)
        host, port = srv.server_address
        print(f"serving on http://{host}:{port}/generate")
        dbg = debug.start_debug_server()
        print(f"debug surface on {dbg.address}"
              f" (/metrics /healthz /statusz /tracez)")

        rng = np.random.RandomState(0)
        # prompts generated BEFORE the threads start: RandomState is
        # not thread-safe, and the seeded demo should be reproducible
        prompts = [rng.randint(0, 1000, 8 + i * 3).tolist()
                   for i in range(12)]
        results = {}

        def client(i):
            body = {"prompt_ids": prompts[i],
                    "max_new_tokens": 24,
                    "temperature": 0.7 if i % 2 else 0.0}
            req = Request(f"http://{host}:{port}/generate",
                          data=json.dumps(body).encode(),
                          headers={"Content-Type": "application/json"})
            with urlopen(req, timeout=600) as r:
                results[i] = json.loads(r.read())

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0

        tokens = sum(len(r["output_ids"]) for r in results.values())
        print(f"{len(results)} clients, {tokens} tokens in {dt:.2f}s "
              f"({tokens / dt:.0f} tok/s aggregate)")
        for i in sorted(results)[:3]:
            r = results[i]
            print(f"  client {i}: ttft {r['ttft_s']:.3f}s "
                  f"latency {r['latency_s']:.3f}s "
                  f"out {r['output_ids'][:8]}...")
        srv.shutdown()
        print(f"engine: {engine.n_steps} decode steps, "
              f"{engine.n_tokens} tokens")
        phases = tracing.rollup(prefix="llm.", exclude=("llm.request",))
        print("phase shares: " + ", ".join(
            f"{k.split('.', 1)[1]}={v['share']:.1%}"
            for k, v in phases.items()))
        debug.stop_debug_server()


if __name__ == "__main__":
    main()
