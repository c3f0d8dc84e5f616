"""On-chip kernel microbench + block autotune: Pallas flash vs XLA dense.

Run (requires a free TPU chip; this one process then owns it):

    python benchmarks/tpu_kernels.py

A single-chain timing with one D2H fetch per measurement folds the host
round-trip of that fetch into every row, which at short L can dwarf the
kernel. The method here removes it:

1. **Slope timing**: each op is timed as two jitted ``lax.scan`` chains of
   N_LO and N_HI data-dependent calls (one D2H fetch each); per-call time is
   the slope ``(T_hi - T_lo) / (N_hi - N_lo)``, which cancels the constant
   per-measurement RTT exactly. The implied RTT is recorded per row as a
   sanity check.
2. **Block autotune**: Mosaic's default BlockSizes are 128/128/128 at every
   L; the sweep times candidate (block_q, block_k_major, block_k) triples
   (single-chain raw ranking — RTT is a shared constant at fixed L, so it
   cannot change the argmin), picks the per-L winner, and writes it to
   ``records/flash_autotune.json``, which
   ``ray_tpu/ops/attention.py`` loads for all production flash calls.

The sweep is time-boxed and runs in evidence-priority order: 2k sweep, 8k sweep, final slope-timed table at all
four L, 1k/4k quick sweeps if time remains.

Reference analog: the reference's fused-attention GPU benchmarks live in its
release suites; on TPU the comparison that matters is Pallas kernel vs the
XLA-fused dense softmax path (`ops/attention.py`).
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N_LO, N_HI = 4, 20
BUDGET_S = float(os.environ.get("KERNEL_BENCH_BUDGET_S", "480"))
_T0 = time.monotonic()


def _left() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _chained(attn_fn, iters: int):
    """jit(q,k,v) -> scalar after ``iters`` data-dependent attention calls."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(q, k, v):
        def body(carry, _):
            o = attn_fn(q + carry, k, v)
            # Fold the output into a tiny scalar the next iteration depends
            # on; the 1e-8 scale keeps q numerically unchanged.
            return (o[0, 0, 0, :8].astype(jnp.float32).sum() * 1e-8
                    ).astype(q.dtype), None

        carry, _ = lax.scan(body, jnp.zeros((), q.dtype), None, length=iters)
        return carry.astype(jnp.float32)

    return run


def _time_once(run, q, k, v, repeats: int) -> float:
    """Median wall seconds for one full chain (compile excluded)."""
    import numpy as np

    float(np.asarray(run(q, k, v)))  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(np.asarray(run(q, k, v)))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _slope_time(attn_fn, q, k, v, repeats: int = 3):
    """(per_call_s | None, implied_rtt_s) via two chain lengths.

    A non-positive slope means RTT jitter swamped the kernel time (short-L
    hazard); rather than clamping — which once turned noise into a committed
    28 PFLOP/s record — retry with more repeats, then report the row invalid
    (per_call None) so no TFLOP/s figure is derived from it.
    """
    run_lo, run_hi = _chained(attn_fn, N_LO), _chained(attn_fn, N_HI)
    for attempt_repeats in (repeats, repeats * 3):
        t_lo = _time_once(run_lo, q, k, v, attempt_repeats)
        t_hi = _time_once(run_hi, q, k, v, attempt_repeats)
        slope = (t_hi - t_lo) / (N_HI - N_LO)
        if slope > 0:
            return slope, max(t_lo - N_LO * slope, 0.0)
    return None, t_lo


def _mosaic_fn(block_q, block_k_major, block_k, causal=True):
    """[B,L,H,D] flash with explicit fwd block sizes."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as mosaic_flash)

    bs = BlockSizes(block_q=block_q, block_k_major=block_k_major,
                    block_k=block_k, block_b=1)

    def fn(q, k, v):
        scale = q.shape[-1] ** -0.5
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        ot = mosaic_flash(qt, kt, vt, causal=causal, sm_scale=scale,
                          block_sizes=bs)
        return ot.transpose(0, 2, 1, 3)

    return fn


def _candidates(seq: int):
    cands = [(128, 128, 128), (256, 256, 256), (512, 512, 512),
             (256, 512, 512), (512, 1024, 512), (512, 256, 256),
             (1024, 1024, 512)]
    return [(bq, bkm, bk) for bq, bkm, bk in cands
            if seq % bq == 0 and seq % bkm == 0 and bkm % bk == 0
            and bq <= seq and bkm <= seq]


def _sweep(seq: int, q, k, v, rows_sweep: list, repeats: int = 2):
    """Raw single-chain ranking of block candidates at one L."""
    results = []
    for bq, bkm, bk in _candidates(seq):
        if _left() < 30:
            break
        try:
            t = _time_once(_chained(_mosaic_fn(bq, bkm, bk), 8), q, k, v,
                           repeats)
        except Exception as e:  # candidate doesn't tile / VMEM blowout
            rows_sweep.append({"seq": seq, "block_q": bq,
                               "block_k_major": bkm, "block_k": bk,
                               "error": repr(e)[:120]})
            continue
        row = {"seq": seq, "block_q": bq, "block_k_major": bkm,
               "block_k": bk, "chain8_ms": round(t * 1e3, 3)}
        rows_sweep.append(row)
        results.append((t, (bq, bkm, bk)))
        print(json.dumps(row))
    return min(results)[1] if results else (128, 128, 128)


def main() -> int:
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"no TPU (got {dev.platform})"}))
        return 1

    from ray_tpu.ops import dense_attention

    batch, heads, head_dim = 4, 8, 128
    dense_fn = functools.partial(dense_attention, causal=True)

    def make_qkv(seq):
        key = jax.random.PRNGKey(seq)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (batch, seq, heads, head_dim)
        return (jax.random.normal(kq, shape, dtype=jnp.bfloat16),
                jax.random.normal(kk, shape, dtype=jnp.bfloat16),
                jax.random.normal(kv, shape, dtype=jnp.bfloat16))

    rows_sweep: list = []
    best: dict = {}

    # Priority 1: sweeps at the two load-bearing lengths.
    for seq in (2048, 8192):
        if _left() < 60:
            break
        q, k, v = make_qkv(seq)
        best[seq] = _sweep(seq, q, k, v, rows_sweep)
        del q, k, v

    # Priority 2: slope-timed final table, tuned flash vs dense.
    rows = []
    for seq in (1024, 2048, 4096, 8192):
        if _left() < 45:
            break
        q, k, v = make_qkv(seq)
        # Nearest swept L supplies the blocks for unswept lengths.
        if best:
            cfg = best.get(seq) or best[min(best, key=lambda s: abs(s - seq))]
        else:
            cfg = (512, 512, 512)
        cfg = tuple(min(c, seq) for c in cfg)
        # fwd FLOPs: 2*L^2*D (QK^T) + 2*L^2*D (PV) per head, halved causal.
        flops = 4.0 * batch * heads * seq * seq * head_dim * 0.5
        t_flash, rtt_f = _slope_time(_mosaic_fn(*cfg), q, k, v)
        row = {"seq": seq, "blocks": list(cfg)}
        if t_flash is None:
            row["invalid_slope"] = True
            row["chain_lo_s"] = round(rtt_f, 4)
        else:
            row.update(flash_ms=round(t_flash * 1e3, 3),
                       flash_tflops=round(flops / t_flash / 1e12, 2),
                       implied_rtt_ms=round(rtt_f * 1e3, 1))
        # Dense materializes the [B,H,L,L] score matrix — skip where it
        # cannot fit (8k: 4*8*8192^2 * 4B ~= 8.6 GB > HBM).
        if seq > 4096:
            row["dense_skip_reason"] = "scores matrix exceeds HBM"
        elif _left() <= 45:
            row["dense_skip_reason"] = "time budget exhausted"
        else:
            t_dense, _ = _slope_time(dense_fn, q, k, v)
            if t_dense is not None:
                row["dense_ms"] = round(t_dense * 1e3, 3)
                row["dense_tflops"] = round(flops / t_dense / 1e12, 2)
                if t_flash is not None:
                    row["speedup"] = round(t_dense / t_flash, 2)
            else:
                row["dense_skip_reason"] = "invalid slope"
        rows.append(row)
        print(json.dumps(row))
        del q, k, v

    # Priority 3: quick sweeps at the remaining lengths.
    for seq in (1024, 4096):
        if _left() < 90:
            break
        q, k, v = make_qkv(seq)
        best[seq] = _sweep(seq, q, k, v, rows_sweep, repeats=1)
        del q, k, v

    ts = int(time.time())
    if best:
        autotune = {
            "note": "fwd-block autotune by benchmarks/tpu_kernels.py; "
                    "loaded by ray_tpu/ops/attention.py flash_block_sizes()",
            "device": str(dev),
            "head_dim": head_dim,
            "ts": ts,
            "best": [{"seq": s, "block_q": b[0], "block_k_major": b[1],
                      "block_k": b[2]} for s, b in sorted(best.items())],
        }
        apath = os.path.join(_REPO, "records", "flash_autotune.json")
        with open(apath, "w") as f:
            json.dump(autotune, f, indent=1)

    record = {
        "metric": "attention_fwd_tflops",
        "unit": "TFLOP/s (bf16, causal, B4 H8 D128)",
        "device": str(dev),
        "method": f"slope timing over scan chains of {N_LO} and {N_HI} "
                  "data-dependent calls (cancels the fetch's RTT); block "
                  "sweep "
                  "ranked by raw chain-8 time (RTT constant at fixed L)",
        "rows": rows,
        "sweep": rows_sweep,
        "best_blocks": {str(s): list(b) for s, b in sorted(best.items())},
        "budget_s": BUDGET_S,
        "elapsed_s": round(time.monotonic() - _T0, 1),
        "ts": ts,
    }
    rpath = os.path.join(_REPO, "records", f"tpu_kernels_{ts}.json")
    with open(rpath, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"record_file": rpath}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
