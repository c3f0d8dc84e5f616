"""Llama training-step throughput + MFU on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}

``vs_baseline`` is MFU / 0.45 — the north-star target from BASELINE.json
("Llama-3-8B DP >= 45% MFU"; the reference ships no TPU numbers, so the MFU
target is the baseline). Runs the real training path in this process: bf16
Llama with flash attention + adam, jitted. It needs a chip: without one it
exits non-zero and prints no number, because a CPU timing under a per-chip
metric name is not a measurement. ``--mode stripe`` is the object-plane
verification bench and needs no accelerator.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
_RECORDS = os.path.join(_REPO, "records")

#: bf16 peak FLOP/s per chip, keyed by the ``device_kind`` jax reports.
#: Source: Google Cloud TPU documentation, per-generation system pages
#: ("TPU v5e": 197 TFLOP/s bf16). A device that is not here is an error,
#: not a default.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e
}


def detect_peak_flops(device) -> float:
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind "
            f"{device.device_kind!r}; add it to bench.PEAK_FLOPS with its "
            f"source") from None


def main():
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    import jax
    import jax.numpy as jnp
    import optax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py --mode train needs a TPU; jax found {dev}")
    peak = detect_peak_flops(dev)

    from ray_tpu.models import LlamaConfig, flops_per_token, init_params, loss_fn

    # ~1.2B params: the largest Llama-3-shaped model that trains
    # comfortably in 16GB HBM (v5e) with bf16 adam state.
    # Sweep knobs: BENCH_BATCH / BENCH_SEQ / BENCH_REMAT /
    # BENCH_CHUNKED_VOCAB. The chunked vocab softmax (ops/chunked_xent.py)
    # skips the ~1 GiB fp32 logits materialization; BENCH_SEQ > 2048 is
    # the long-context config (flash attention + remat + chunked CE).
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    cfg = LlamaConfig(vocab_size=32768, d_model=2048, n_layers=16,
                      n_heads=16, n_kv_heads=8, d_ff=8192,
                      max_seq_len=max(2048, seq), dtype=jnp.bfloat16)
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    chunked_vocab = int(os.environ.get("BENCH_CHUNKED_VOCAB", "0"))

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg, remat=remat,
                              chunked_vocab=chunked_vocab))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # Warmup / compile, then a timed region closed by block_until_ready.
    params, opt_state, loss = step(params, opt_state, tokens)
    first_loss = float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    jax.block_until_ready((params, opt_state, loss))
    dt = time.perf_counter() - t0

    tok_per_sec = batch * seq * steps / dt
    mfu = flops_per_token(cfg, seq) * tok_per_sec / peak
    print(json.dumps({
        "metric": f"llama_{cfg.param_count()/1e9:.1f}B_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "first_loss": round(first_loss, 3),
            "loss": round(float(loss), 4),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "params_b": round(cfg.param_count() / 1e9, 3),
            "batch": batch, "seq": seq, "steps": steps,
            "remat": remat, "chunked_vocab": chunked_vocab,
            "step_time_s": round(dt / steps, 4),
        },
    }))


def _dispatch():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--mode", default="train", choices=("train", "stripe"),
        help="train: Llama step throughput (default). stripe: object "
             "plane v2 verification — striped-broadcast source share + "
             "over-arena serve-from-spill ratio, from chunk events "
             "(writes records/STRIPE_r18.json).")
    args, _ = ap.parse_known_args()
    if args.mode == "stripe":
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks import stripe_share

        stripe_share.main()
    else:
        main()


if __name__ == "__main__":
    _dispatch()
