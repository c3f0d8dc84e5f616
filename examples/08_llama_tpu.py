"""Flagship: Llama training step + KV-cached generation on one chip.

On a TPU host this trains the 1.1B benchmark configuration (what
``bench.py`` measures, with MFU); anywhere else it scales the model down
and runs on CPU so the example stays runnable.

Reference-Ray equivalent: the torch-based ``doc/source/train/examples``
LLM fine-tuning examples.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools
import time

import jax
import jax.numpy as jnp
import optax


def main():
    if os.environ.get("RAY_TPU_JAX_PLATFORM") == "cpu":
        # Off-TPU:
        #   RAY_TPU_JAX_PLATFORM=cpu python examples/08_llama_tpu.py
        # (this script imports jax without ray_tpu, so it applies the
        # variable itself)
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    print("device:", dev)

    from ray_tpu.models import (LlamaConfig, generate_greedy, init_params,
                                loss_fn)

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32768, d_model=2048, n_layers=16,
                          n_heads=16, n_kv_heads=8, d_ff=8192,
                          max_seq_len=2048, dtype=jnp.bfloat16)
        batch, seq, steps = 4, 2048, 10
    else:
        cfg = LlamaConfig(vocab_size=512, d_model=128, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=256,
                          max_seq_len=256, dtype=jnp.float32)
        batch, seq, steps = 2, 128, 3
    print(f"params: {cfg.param_count()/1e9:.2f}B")

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg,
                              remat=not on_tpu))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, loss = step(params, opt_state, tokens)  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    final = float(loss)  # host fetch fences the device work
    dt = time.perf_counter() - t0
    tok_s = batch * seq * steps / dt
    print(f"loss {final:.3f}; {tok_s:,.0f} tokens/s on {dev.platform}")

    # KV-cached greedy decode off the trained weights.
    prompt = tokens[:1, :8]
    out = generate_greedy(params, prompt, cfg, max_new=16)
    print("generated token ids:", out[0].tolist())


if __name__ == "__main__":
    main()
