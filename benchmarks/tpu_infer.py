"""TPU inference benchmark: KV-cached decode throughput + prefill on one chip.

The reference establishes its inference story in ``release/serve_tests`` and
the vLLM-backed serving suites (`/root/reference/release/llm_tests`); the
TPU-native equivalent is the scan-based KV-cached decode loop in
``ray_tpu/models/llama.py`` (`generate_greedy`). This records:

- decode tokens/s per chip across a batch sweep (the serving-throughput
  number; decode is HBM-bandwidth-bound, so batch scaling is the story),
- per-step decode latency (the interactive-latency number),
- estimated model-bandwidth utilization (MBU = bytes-touched/step over the
  chip's HBM bandwidth), the decode analogue of training MFU,
- batch-1 prefill tokens/s at 2k context (compute-bound, MXU-limited).

Writes ``records/tpu_infer_<ts>.json``. Runs in this one process, which
then owns the chip; each timed region ends in a host fetch of the generated
tokens.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root flagship bench: records dir + peak-flops table

#: HBM bandwidth per chip in GB/s, keyed by the ``device_kind`` jax
#: reports (same spellings and source as ``bench.PEAK_FLOPS``).
HBM_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5": 2765.0,
    "TPU v6 lite": 1640.0,
}


def _save(record: dict) -> str:
    os.makedirs(bench._RECORDS, exist_ok=True)
    path = os.path.join(bench._RECORDS, f"tpu_infer_{int(time.time())}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return path


def main():
    # TPU_INFER_CPU_SMOKE=1: run the ENTIRE harness on CPU with tiny
    # shapes — every code path (sweep, int8, engine, prefill, record
    # assembly) executes, so a latent bug cannot wait for a chip run to
    # surface. Its numbers mean nothing and are marked cpu_smoke.
    smoke = os.environ.get("TPU_INFER_CPU_SMOKE") == "1"
    if smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import LlamaConfig, generate_greedy

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not smoke:
        print(json.dumps({"error": f"not a TPU: {dev}"}))
        return 1

    if smoke:
        cfg = LlamaConfig(vocab_size=512, d_model=64, n_layers=2,
                          n_heads=4, n_kv_heads=2, d_ff=128,
                          max_seq_len=128, dtype=jnp.float32)
    else:
        cfg = LlamaConfig(vocab_size=32768, d_model=2048, n_layers=16,
                          n_heads=16, n_kv_heads=8, d_ff=8192,
                          max_seq_len=4096, dtype=jnp.bfloat16)
    from ray_tpu.models import init_params

    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = cfg.param_count()
    # the smoke's ratios are against a v5e's peaks, and mean nothing
    kind = "TPU v5 lite" if smoke else dev.device_kind
    hbm_gbps = HBM_GBPS[kind]
    peak_flops = bench.PEAK_FLOPS[kind]

    prompt_len, max_new = (16, 8) if smoke else (128, 256)
    rows = []
    for batch in (1, 2) if smoke else (1, 8, 32):
        prompt = jax.random.randint(jax.random.PRNGKey(batch),
                                    (batch, prompt_len), 0, cfg.vocab_size)
        out = generate_greedy(params, prompt, cfg, max_new=max_new)
        np.asarray(out)  # warmup + compile, fenced by the fetch
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = generate_greedy(params, prompt, cfg, max_new=max_new)
        np.asarray(out)  # host fetch closes the timed region
        dt = (time.perf_counter() - t0) / reps
        step_ms = dt / max_new * 1e3
        tok_s = batch * max_new / dt
        # Bytes touched per decode step: full bf16 params + the KV cache
        # prefix read/written across layers (2 bytes, k+v).
        mid_pos = prompt_len + max_new // 2
        kv_bytes = (batch * mid_pos * cfg.n_kv_heads * cfg.head_dim
                    * 2 * 2 * cfg.n_layers)
        mbu = (n_params * 2 + kv_bytes) / (hbm_gbps * 1e9) / (dt / max_new)
        rows.append({"batch": batch, "decode_tok_s": round(tok_s, 1),
                     "step_ms": round(step_ms, 3), "mbu": round(mbu, 4)})
        print(f"batch {batch}: {tok_s:.1f} tok/s, {step_ms:.2f} ms/step, "
              f"MBU {mbu:.3f}", file=sys.stderr)

    # Weight-only int8 at the champion batch: decode is HBM-bound, so
    # halving weight bytes should approach 2x tokens/s (ops/quant.py).
    from ray_tpu.ops.quant import quantize_params, quantized_nbytes

    champ_batch = max(rows, key=lambda r: r["decode_tok_s"])["batch"]
    qparams = quantize_params(params)
    qprompt = jax.random.randint(jax.random.PRNGKey(99),
                                 (champ_batch, prompt_len), 0,
                                 cfg.vocab_size)
    np.asarray(generate_greedy(qparams, qprompt, cfg, max_new=max_new))
    t0 = time.perf_counter()
    for _ in range(3):
        out = generate_greedy(qparams, qprompt, cfg, max_new=max_new)
    np.asarray(out)
    qdt = (time.perf_counter() - t0) / 3
    int8_row = {
        "batch": champ_batch,
        "decode_tok_s": round(champ_batch * max_new / qdt, 1),
        "step_ms": round(qdt / max_new * 1e3, 3),
        "weight_bytes_ratio": round(
            quantized_nbytes(qparams) / quantized_nbytes(params), 3),
    }
    print(f"int8 batch {champ_batch}: {int8_row['decode_tok_s']} tok/s",
          file=sys.stderr)

    # Continuous batching: S concurrent requests sharing every decode
    # step (models/engine.py) — the serving-throughput shape, measured
    # as aggregate tokens/s across staggered requests.
    from ray_tpu.models.engine import GenerationEngine

    eng_slots = 8
    eng = GenerationEngine(params, cfg, max_slots=eng_slots,
                           max_len=prompt_len + max_new + 8)
    rng = np.random.default_rng(0)
    for r in range(eng_slots):
        eng.submit(f"r{r}", rng.integers(
            0, cfg.vocab_size, prompt_len).tolist(),
            max_new_tokens=max_new)
    # warmup: one step compiles prefill + step_all
    eng.step()
    t0 = time.perf_counter()
    produced = 0
    while eng.has_work():
        produced += sum(1 for _, tok in eng.step() if tok is not None)
    edt = time.perf_counter() - t0
    engine_row = {"slots": eng_slots, "agg_decode_tok_s":
                  round(produced / edt, 1),
                  "requests": eng_slots, "max_new": max_new}
    print(f"engine x{eng_slots}: {engine_row['agg_decode_tok_s']} "
          f"aggregate tok/s", file=sys.stderr)

    # Prefill: compute-bound forward over 2k context, batch 1.
    import functools

    from ray_tpu.models.llama import forward

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def prefill(params, tokens, cfg):
        return forward(params, tokens, cfg, remat=False)

    ptoks = jax.random.randint(jax.random.PRNGKey(7),
                               (1, 64 if smoke else 2048), 0,
                               cfg.vocab_size)
    np.asarray(prefill(params, ptoks, cfg)[0, -1, :8])
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        logits = prefill(params, ptoks, cfg)
    np.asarray(logits[0, -1, :8])
    pdt = (time.perf_counter() - t0) / reps
    prefill_tok_s = ptoks.shape[1] / pdt
    prefill_mfu = 2 * n_params * prefill_tok_s / peak_flops

    champ = max(rows, key=lambda r: r["decode_tok_s"])
    record = {
        "metric": f"llama_{n_params/1e9:.1f}B_decode_tokens_per_sec_per_chip",
        "value": champ["decode_tok_s"],
        "unit": "tokens/sec/chip",
        "extra": {
            "champion_batch": champ["batch"],
            "batch_sweep": rows,
            "int8_weight_only": int8_row,
            "continuous_batching": engine_row,
            "prefill_tok_s_b1_2k": round(prefill_tok_s, 1),
            "prefill_mfu": round(prefill_mfu, 4),
            "device": str(dev),
            "hbm_gbps_assumed": hbm_gbps,
            "params_b": round(n_params / 1e9, 3),
            "prompt_len": prompt_len, "max_new": max_new,
            "method": "KV-cached lax.scan greedy decode; host fetch fence",
        },
        "ts": time.time(),
    }
    if smoke:
        record["extra"]["cpu_smoke"] = True
        print(json.dumps(record))
        return 0
    record["extra"]["record_file"] = _save(record)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
