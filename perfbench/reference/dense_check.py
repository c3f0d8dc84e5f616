"""The reference check of a dense decoder served by ``PagedEngine``
(the two Mistral-7B-v0.3 configurations name it; the contract is in
``perfbench/reference/__init__.py``).

One seeded request through the engine's own programs, against the plain
reference's full forward: the prompt's last-position logits row for row, and
every emitted token's margin under the reference's best logit at its
position.

This file, and no other that a benchmark run executes, imports a private name
of the program: ``ray_tpu.models.engine._prefill_one`` is the jitted prefill that
``PagedEngine._admit`` calls (same function object, so the same compiled
program at the same bucket), and nothing public returns its logits row: the
engine keeps only the token picked from it. A family whose prefill returns
other state brings a check of its own and this import stays here.
"""

from __future__ import annotations

#: worst |engine - reference| over the 32768 logits of the prompt's last
#: position, in units of that row's standard deviation; and how far under the
#: reference's best logit an emitted token's reference logit may sit, in the
#: same units. bf16 keeps 8 bits: each product rounds by up to 2**-9 relative
#: and 16 layers of residual sums carry those roundings into logits of
#: standard deviation about 1. On the chip that measured 0.056 sigma for the
#: worst of 32768 logits (about 4.3 standard deviations of a per-logit error
#: near 0.013) and 0.037 sigma for the worst margin (my chip run, PR 23).
#: PR 23 wrote that a path with 3-4 fewer mantissa bits "errs 8-16 times as
#: much and lands far outside"; measured by PR 27 (below), int8 weights err
#: 2.6 times as much, one seed of sixteen inside the 0.15, and int8 pages are
#: not seen at all. These two limits stand as PR 23 set them; the third,
#: below, is the one that holds the weights' control out.
REF_ROW_TOL_SIGMA = 0.15
REF_MARGIN_TOL_SIGMA = 0.10
#: root mean square of (engine - reference) over that row, in the same units:
#: added by PR 27 because the worst logit of 32768 is an extreme value and
#: does not hold the control out. Readings (my chip runs, PR 27, at the cell's
#: own size): 50 sound runs on 44 seeds read 0.0126-0.0144 sigma here and
#: 0.048-0.066 for the worst logit; the control (``dense_control.py``: the
#: engine on the program's own per-channel int8 weights) on 16 seeds read
#: 0.0351-0.0396 here, but 0.149-0.193 for the worst logit, and one seed of
#: the sixteen passed the 0.15 above. Weight-only int8 is inherently only
#: some 2.6 times bf16's own roundings (each bf16 layer rounds its
#: activations a dozen times; int8 weights add one coarser rounding a
#: product), so the two stand 2.4 times apart, not three: the limit is their
#: geometric middle, 1.57 times the sound runs' largest and 1.56 times under
#: the control's smallest, which a reading this steady (+-7 % over 50 runs)
#: can bear. int8 PAGES (``kv_dtype="int8"``) pass all three readings: 24
#: tokens decoded over 200 cached positions do not show them (PERF.md 7).
REF_RMS_TOL_SIGMA = 0.0225


def prefill_row(engine, prompt):
    """The float32 logits row the ENGINE's prefill program gives at the
    prompt's last position, at the bucket ``_admit`` would pad it to."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.engine import _prefill_one

    pad = next(b for b in list(engine._prefill_buckets) + [engine.max_len]
               if b >= len(prompt))
    padded = jnp.asarray(prompt + [0] * (pad - len(prompt)), jnp.int32)
    first, _ = _prefill_one(engine.params, padded, len(prompt), engine.max_len,
                            engine.cfg, engine.cos, engine.sin, pad)
    return np.asarray(first.astype(jnp.float32))


def compare(engine_row, prompt, emitted, reference_params, config, shape):
    """The engine's row and emitted tokens against the plain reference's
    full forward over ``reference_params`` (the program's tree: the check
    passes the engine's own, the control the weights as they were before it
    rounded the engine's)."""
    import numpy as np

    from perfbench.manifest import resolve

    ref = resolve(config["program"]["reference"])
    to_ref = resolve(config["program"]["reference_weights"])
    seq = list(prompt) + list(emitted)
    rows = np.asarray(ref(to_ref(reference_params), seq, shape))
    ref_row = rows[len(prompt) - 1]
    picked = rows[np.arange(len(prompt) - 1, len(seq) - 1),
                  np.asarray(emitted)]
    best = rows[len(prompt) - 1:len(seq) - 1].max(axis=-1)
    sigma = float(ref_row.std())
    err = engine_row - ref_row
    readings = [{"name": name, "value": float(value), "limit": tol * sigma}
                for name, value, tol in (
        ("prefill_max_abs_err", np.abs(err).max(), REF_ROW_TOL_SIGMA),
        ("max_margin", (best - picked).max(), REF_MARGIN_TOL_SIGMA),
        ("prefill_rms_err", np.sqrt(np.mean(err ** 2)), REF_RMS_TOL_SIGMA))]
    return {
        "ok": all(r["value"] <= r["limit"] for r in readings),
        "finite": bool(np.isfinite(rows).all()
                       and np.isfinite(engine_row).all()),
        "readings": readings,
        "notes": {"ref_logit_std": sigma,
                  "exact_argmax": int((best == picked).sum()),
                  "tokens": len(emitted)},
    }


def check(engine, prompt, emitted, config, shape) -> dict:
    return compare(prefill_row(engine, prompt), prompt, emitted,
                   engine.params, config, shape)
