"""The reference check of the latent-attention / zero-expert family served by
``PagedEngine`` (``longcat-flash-omni-serve1`` names it; the contract is in
``perfbench/reference/__init__.py``).

Two seeded requests through the engine's own programs, each against ONE pass
of the plain reference (float32, the EXPANDED attention form over the whole
sequence). As in ``nemotron_h_check``, a routing decision at a near tie swaps
a whole expert (or a zero expert's ``gate x input`` for nothing), so the
reference runs UNDER THE ROUTING THE TIMED PROGRAMS THEMSELVES CHOSE at every
position (its own scores for the imposed experts), and the routing is held to
a reading of its own.

**The contract's request** (200 tokens, 24 streamed). The prompt's routing
comes from the prefill program (the one ``_admit`` calls, which returns every
position's chosen experts beside its logits row); the decoded positions' from
the engine, which is given the request AGAIN here (idle and locked) and leaves
each step's chosen experts on the device (``last_routing``).

(a) ``routing_far_disagreements``: at every expert layer and position the
    reference, fed the same upstream routing, makes its own choice; where
    that differs from the program's, the program's worst pick must lie within
    ``ROUTE_TIE_TOL`` (as a share of the reference's own cut-off in score +
    bias) of that cut-off. The count of decisions that lie farther, over
    both requests, is the reading; its limit is 0.
(b) ``prefill_max_abs_err`` / ``prefill_rms_err``: the prefill's row at the
    prompt's last position against the reference's.
(c) ``rerun_token_mismatches``: tokens of the re-run that differ from the
    streamed ones; limit 0 (same programs, same inputs).
(d) ``max_margin``: how far under the reference's best logit each of the 24
    streamed tokens' reference logit sits, over prompt + emitted.

**The probe** (``config["probe"]``: 8192 tokens, 8 tokens decoded), because
200 positions never leave one chunk nor make the latent read long. The engine
is idle and locked; the probe goes through the engine's own four-chunk
prefill (EXPANDED form, a later chunk re-expanding the earlier positions) and
then, submitted, through 8 of its own decode steps (ABSORBED form over 129
pages of the latent pool):

(e) ``probe_prefill_max_abs_err`` / ``probe_prefill_rms_err``: the prefill's
    last row against the reference under the program's routing.
(f) ``probe_max_margin``: each of the 8 tokens' margin against the reference
    over prompt + emitted. Every token after the first comes from the
    absorbed step over the rows the admission scattered: a wrong absorbed
    form, a latent row on the wrong page or an unscaled latent moves every
    later row (``tests/perfbench/test_longcat_check.py`` corrupts the latent
    write and sees it fail), and shows in (a) at the decoded positions too.

This file, and no other that a benchmark run executes, reads what only this
family has: ``ray_tpu.models.longcat_flash.prefill`` is public, the engine's
``last_routing`` is read as the hybrid family's is.
"""

from __future__ import annotations

#: Readings all these limits were set from (my chip runs, PR 34): 23 sound
#: runs on 23 seeds (15 in ``longcat_flash_control.py``'s calls, 8 runs of the
#: cell) and the int8 control on the same 15 seeds, a third of them over
#: 2**31. Logits have sigma 0.994-1.009.
#:
#: (a) how far under the reference's own cut-off (softmax score + bias, a
#: number near 0.0035) the program's worst pick may lie, as a share of that
#: cut-off, and still be a near tie. 9 % of the 33 688 decisions of a run
#: (3 007-3 177) disagree with the float32 reference's, all at near ties:
#: sound worst 0.028-0.039; the control 0.064-0.097, 18-19 % of its decisions
#: disagreeing and 28-58 of them over 0.05. The worst of thirty thousand is
#: an extreme value: the limit is the geometric middle of the sound runs'
#: largest and the control's smallest, 1.29 times from both. The control does
#: not rest on it: it fails (b) and (e) on every seed.
ROUTE_TIE_TOL = 0.05
#: (b), (e) worst and root-mean-square |program - reference| over the 16384
#: logits of the prompt's last position, in units of that row's standard
#: deviation. Sound: RMS 0.0082-0.0097 on the contract's request, 0.0081-
#: 0.0092 on the probe (20 sublayers: the 16-layer dense cell reads 0.013-
#: 0.014), worst 0.030-0.043 / 0.031-0.041; control: RMS 0.0214-0.0263 /
#: 0.0194-0.0237, worst 0.089-0.123 / 0.077-0.102: 2.2-2.6 times apart, as
#: weight-only int8 is. Each limit is the geometric middle of the sound runs'
#: largest and the control's smallest: 1.41 times from both (RMS), 1.34 times
#: (worst). The RMS holds the control out on all 15 seeds, on both requests.
REF_ROW_TOL_SIGMA = 0.057
REF_RMS_TOL_SIGMA = 0.0138
#: (d), (f) the streamed tokens' margin, in the same unit. Sound: 0-0.035 on
#: the contract's 24 tokens (22-24 of them the reference's argmax), 0-0.021
#: on the probe's 8; the control 0-0.044 and 0-0.013 (it is not held out by
#: this reading, as no family's check's is: a flipped token at a near tie
#: says nothing of precision). An unscaled or misplaced latent scatter, or an
#: absorbed form without its rotary key, reads over it at toy widths
#: (``tests/perfbench/test_longcat_check.py``). The limit is 2.5 times the
#: sound runs' largest.
REF_MARGIN_TOL_SIGMA = 0.088


def probe_sizes(config: dict, shape: dict):
    """(prompt length, tokens decoded) of the probe, as run."""
    toy = shape["hidden_size"] != config["hidden_size"]
    p = (config["rehearsal"] if toy else config)["probe"]
    return int(p["prompt_len"]), int(p["new_tokens"])


def probe_prompt(prompt, n: int, vocab: int):
    """The probe's token ids: seeded by the contract's prompt, which the
    run's seed drew."""
    import random

    rng = random.Random((prompt[0] << 40) | (prompt[1] << 20) | prompt[2])
    return [rng.randrange(vocab) for _ in range(n)]


def _through_engine(engine, rid, prompt, new):
    """The request through the engine's own prefill and decode steps: (the
    prefill's float32 row, the streamed tokens, the chosen experts [layers,
    len(prompt) + tokens - 1, k] of the prefill program and of the steps)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import longcat_flash as longcat

    row, _, routing = longcat.prefill(engine.params, prompt, engine.max_len,
                                      engine.cfg, keep_routing=True)
    routes = [routing]
    slot = engine.slots.index(None)     # where ``_admit_one`` will put it
    engine.submit(rid, prompt, max_new_tokens=new)
    tokens = []
    while engine.has_work():
        before = len(tokens)
        tokens += [tok for r, tok in engine.step()
                   if r == rid and tok is not None]
        if len(tokens) > before and len(tokens) > 1:   # a decode step landed
            routes.append(np.asarray(engine.last_routing)[:, slot][:, None])
    return (np.asarray(row.astype(jnp.float32)), tokens,
            np.concatenate(routes, axis=1))


def program_out(engine, prompt, emitted, config, shape):
    """What the engine's own programs produce for the contract's request
    (given again) and for the probe."""
    n, m = probe_sizes(config, shape)
    probe = probe_prompt(prompt, n, shape["vocab_size"])
    return {"request": _through_engine(engine, "reference-check", prompt,
                                       len(emitted)),
            "probe": probe,
            "probed": _through_engine(engine, "reference-probe", probe, m)}


def _against(fwd, ref_w, shape, prompt, took, row, routing, names, tols):
    """One request's readings: the prefill row's errors and the streamed
    tokens' margin (each limit in units of the row's sigma), and what the
    reference says of the imposed routing."""
    import numpy as np

    n, m = len(prompt), len(took)
    seq = (list(prompt) + list(took))[:routing.shape[1]]
    ref = fwd(ref_w, seq, shape, routing=routing,
              rows=np.arange(n - 1, len(seq)))
    rows = np.asarray(ref["logits"])
    sigma = float(rows[0].std())
    err = row - rows[0]
    k = min(m, len(rows))
    picked = rows[np.arange(k), np.asarray(took[:k])]
    best = rows[:k].max(axis=-1)
    values = (np.abs(err).max(), np.sqrt(np.mean(err ** 2)),
              (best - picked).max())
    own = np.sort(np.asarray(ref["own_routing"]), -1)
    return {
        "readings": [{"name": nm, "value": float(v), "limit": tol * sigma}
                     for nm, v, tol in zip(names, values, tols)],
        "sigma": sigma, "exact": int((best == picked).sum()),
        "under": np.asarray(ref["under"]),
        "differ": (own != np.sort(routing, -1)).any(-1),
        "finite": bool(np.isfinite(rows).all() and np.isfinite(row).all())}


def compare(program, prompt, emitted, reference_params, config, shape):
    """The program's rows, tokens and routing against the plain reference
    over ``reference_params`` (the program's tree: the check passes the
    engine's own, the control the weights as they were before it rounded the
    engine's)."""
    import time

    import numpy as np

    from perfbench.manifest import resolve

    fwd = resolve(config["program"]["reference_forward"])
    ref_w = resolve(config["program"]["reference_weights"])(reference_params)
    tols = (REF_ROW_TOL_SIGMA, REF_RMS_TOL_SIGMA, REF_MARGIN_TOL_SIGMA)
    row, rerun, routing = program["request"]
    t0 = time.perf_counter()
    a = _against(fwd, ref_w, shape, prompt, emitted, row, routing,
                 ("prefill_max_abs_err", "prefill_rms_err", "max_margin"),
                 tols)
    t1 = time.perf_counter()
    prow, took, prouting = program["probed"]
    b = _against(fwd, ref_w, shape, program["probe"], took, prow, prouting,
                 ("probe_prefill_max_abs_err", "probe_prefill_rms_err",
                  "probe_max_margin"), tols)
    t2 = time.perf_counter()
    mismatches = sum(x != y for x, y in zip(rerun, emitted)) \
        + abs(len(rerun) - len(emitted))
    want = probe_sizes(config, shape)[1]
    under = np.concatenate([a["under"].ravel(), b["under"].ravel()])
    differ = np.concatenate([a["differ"].ravel(), b["differ"].ravel()])
    readings = [
        {"name": "routing_far_disagreements",
         "value": float((under > ROUTE_TIE_TOL).sum()), "limit": 0.0},
        {"name": "rerun_token_mismatches", "value": float(mismatches),
         "limit": 0.0},
        {"name": "probe_tokens_missing", "value": float(abs(want - len(took))),
         "limit": 0.0}] + a["readings"] + b["readings"]
    return {
        "ok": all(r["value"] <= r["limit"] for r in readings),
        "finite": a["finite"] and b["finite"],
        "readings": readings,
        "notes": {"ref_logit_std": a["sigma"], "probe_logit_std": b["sigma"],
                  "exact_argmax": a["exact"], "tokens": len(emitted),
                  "probe_len": len(program["probe"]),
                  "probe_exact_argmax": b["exact"],
                  "routing_decisions": int(differ.size),
                  "routing_disagreements": int(differ.sum()),
                  "routing_worst_under": float(under.max()),
                  "route_tie_tol": ROUTE_TIE_TOL,
                  "reference_s": t1 - t0, "probe_reference_s": t2 - t1},
    }


def check(engine, prompt, emitted, config, shape) -> dict:
    return compare(program_out(engine, prompt, emitted, config, shape),
                   prompt, emitted, engine.params, config, shape)
