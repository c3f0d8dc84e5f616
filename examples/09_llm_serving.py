"""LLM serving: continuous batching, streaming tokens, speculative decode.

Reference-Ray equivalent: the vLLM-backed ``serve`` LLM examples — here
the engine is framework-native (``ray_tpu/models/paged.py``) and the
speculative decoder is ``ray_tpu/models/speculative.py``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU demo. The head and every worker inherit this pin: take the line out
# (and ask for a TPU grant) to run on a chip.
os.environ.setdefault("RAY_TPU_JAX_PLATFORM", "cpu")

import asyncio

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import LlamaConfig, generate_speculative, init_params
from ray_tpu.serve.llm import build_llm_app


def tiny_model():
    cfg = LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=256,
                      dtype=jnp.float32)
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def main():
    ray_tpu.init(num_cpus=4, probe_tpu=False)
    # K/V live in a shared page pool, here with prefix caching. 48 pages
    # of 8 hold less than 4 slots x 128 positions: short requests stop
    # paying for worst-case length (leave num_pages out and the pool is
    # sized so that no request ever waits for memory).
    handle = serve.run(build_llm_app(tiny_model, max_slots=4,
                                     max_len=128,
                                     num_pages=48, page_size=8,
                                     enable_prefix_cache=True),
                       name="llm", route_prefix="/generate")

    # Concurrent unary requests share every decode step (continuous
    # batching): a long generation never blocks a short one. The shared
    # 8-token prefix (one full page) exercises the prefix cache: later
    # requests borrow the first request's prefix pages and prefill only
    # their suffix.
    shared = [9, 8, 7, 6, 5, 4, 3, 2]
    futs = [handle.remote({"prompt": shared + [1 + i],
                           "max_new_tokens": 8 + i * 4})
            for i in range(3)]
    for i, f in enumerate(futs):
        print(f"request {i}:", f.result(timeout=120)["tokens"])

    # Token streaming: chunks arrive as the engine emits them.
    async def stream_demo():
        toks = []
        async for tok in handle.stream({"prompt": [9, 8, 7],
                                        "max_new_tokens": 6,
                                        "stream": True}):
            toks.append(tok)
        return toks

    print("streamed:", asyncio.run(stream_demo()))

    # Speculative decoding: a REAL draft — the target's first layer via
    # truncated_draft (the cheap-draft construction when no distilled
    # checkpoint exists) — proposes, the target verifies. Output is
    # EXACTLY the target's greedy decode; the draft's acceptance rate
    # (< 1 here, it is half the model) sets how many tokens each target
    # forward yields.
    from ray_tpu.models.speculative import truncated_draft

    params, cfg = tiny_model()
    draft_params, draft_cfg = truncated_draft(params, cfg, 1)
    prompt = jnp.asarray([[5, 6, 7]], jnp.int32)
    toks, stats = generate_speculative(params, draft_params, prompt, cfg,
                                       draft_cfg, max_new=16, k=4)
    print("speculative:", toks[0].tolist())
    print(f"  acceptance={stats['acceptance_rate']:.2f} "
          f"tokens/target-forward={stats['tokens_per_target_forward']:.2f}")

    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
