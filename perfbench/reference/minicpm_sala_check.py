"""The reference check of the lightning / block-sparse family served by
``PagedEngine`` (``minicpm-sala-9b-serve1`` names it; the contract is in
``perfbench/reference/__init__.py``).

Two seeded requests through the engine's own programs, each against ONE pass
of the plain reference.

**The contract's request** (200 tokens, 24 streamed): the sparse layers stay
in their dense regime, so this reading sees the chunked prefill, the
lightning state AT the prompt's end handed into the slot, the recurrence of
the decode step and the page writes, and nothing of the selection.

(a) ``prefill_max_abs_err`` / ``prefill_rms_err``: the prefill's row at the
    prompt's last position against the reference's.
(b) ``max_margin``: how far under the reference's best logit each of the 24
    streamed tokens' reference logit sits, over prompt + emitted. The first
    token comes from the prefill, every later one from the timed decode step
    over the state the admission wrote and the pages it scattered: a wrong
    state hand-over or recurrence moves every later row
    (``tests/perfbench/test_sala_check.py`` corrupts the state write and sees
    it fail).

**The probe** (``config["probe"]``: 12288 tokens, 192 blocks, 31 freely chosen
of 159; 8 tokens decoded), because 200 tokens never reach ``dense_len``. The
engine is idle and locked; the probe goes through the engine's own chunked
prefill (the program ``_admit_one`` calls, which returns every position's
chosen blocks beside its logits row) and then, submitted, through 8 of its own
decode steps, each of which leaves its chosen blocks on the device
(``last_selection``). The reference then runs UNDER THE SELECTION THE TIMED
PROGRAMS CHOSE, with its own scores for every decision:

(c) ``selection_far_disagreements``: at every sparse layer, position past
    ``dense_len`` and K/V head the reference makes its own choice; the
    program's worst chosen block must lie within ``SELECT_TIE_TOL`` (as a share
    of the reference's own cut-off, its 64th block score) of that cut-off: for
    one swap at the edge that IS the reference's gap between its 64th and 65th
    block, a near tie. A choice that lacks a forced block counts as far. The
    count of decisions that lie farther is the reading; its limit is 0.
    (Issue 32 asked for a free-running reference here; a free-running one
    carries every earlier swap in its hidden state, so its later
    disagreements need not sit at ties at all. Imposing the upstream
    selection isolates each decision, as ``nemotron_h_check`` does for
    routing, and costs one pass of the reference in place of two.)
(d) ``probe_prefill_max_abs_err`` / ``probe_prefill_rms_err``: the prefill's
    last row against the reference under the program's selection.
(e) ``probe_max_margin``: each of the 8 tokens' margin against the reference
    over prompt + emitted, under the selection of the prefill and of the
    engine's own decode steps.

This file, and no other that a benchmark run executes, imports a private name
of the program: ``ray_tpu.models.minicpm_sala.prefill`` is public, the
engine's ``last_selection`` is read as ``last_routing`` is.
"""

from __future__ import annotations

#: Readings all these limits were set from (my chip runs, PR 32): 38 sound
#: runs on 21 seeds (12 in ``minicpm_sala_control.py``'s call, 26 runs of the
#: cell on 9) and the int8 control on the same 12 seeds. Logits have sigma 0.0623-
#: 0.0628 (the head divides by hidden_size / dim_model_base = 16).
#:
#: (c) how far under the reference's own cut-off the program's worst pick may
#: lie, as a share of that cut-off, and still be a near tie. Seeded weights
#: score the compressed keys almost alike (softmax over 770 keys, +-18 % about
#: uniform), so 26-27 % of the 32 824 decisions of a probe disagree with the
#: float32 reference's, all at near ties: sound worst 0.0028-0.0036 (mean
#: 0.0032); the control 0.0046-0.0063, 45 % of its decisions disagreeing. The
#: worst of nine thousand is an extreme value: the limit stands 1.24 times
#: over the sound runs' largest and just under the smallest of the 12
#: controls. The control does not rest on it: it fails (a) and (d) below by
#: a wide distance on every seed.
SELECT_TIE_TOL = 0.0045
#: (a), (d) worst and root-mean-square |program - reference| over the 73448
#: logits of the prompt's last position, in units of that row's standard
#: deviation. Sound: RMS 0.0112-0.0119 on both requests (16 layers, as the
#: dense cell's 0.013-0.014), worst 0.045-0.059; control: RMS 0.0191-0.0202,
#: worst 0.079-0.102: 1.6-1.7 times apart, as weight-only int8 is. Each limit
#: is the geometric middle of the sound runs' largest and the control's
#: smallest: 1.26 times from both (RMS), 1.15 times (worst). The RMS is what
#: holds the control out, on all 12 seeds, on the contract's request and on
#: the probe alike.
REF_ROW_TOL_SIGMA = 0.068
REF_RMS_TOL_SIGMA = 0.015
#: (b), (e) the streamed tokens' margin, in the same unit. Sound: 0-0.024 on
#: the contract's 24 tokens (21-24 of them the reference's argmax), 0-0.034
#: on the probe's 8; the control 0-0.048 and 0-0.025 (it is not held out by
#: this reading, as the dense and the hybrid check's is not: a flipped token
#: at a near tie says nothing of precision). A zeroed or misplaced lightning
#: state reads over it at toy widths (``tests/perfbench/test_sala_check.py``).
#: The limit is 2.5 times the sound runs' largest.
REF_MARGIN_TOL_SIGMA = 0.085


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


def program_out(engine, prompt, emitted, config, shape):
    """What the engine's own programs produce: the contract prompt's prefill
    row; the probe's prompt, prefill row, streamed tokens and the chosen
    blocks [sparse layers, len(probe) + tokens - 1, kvh, topk] of its prefill
    and of the engine's decode steps."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import minicpm_sala as sala

    row = sala.prefill(engine.params, prompt, engine.max_len, engine.cfg)[0]
    n, m = probe_sizes(config, shape)
    probe = probe_prompt(prompt, n, shape["vocab_size"])
    out = sala.prefill(engine.params, probe, engine.max_len, engine.cfg,
                       keep_chosen=True)
    chosen = [out[3]]
    slot = engine.slots.index(None)     # where ``_admit_one`` will put it
    engine.submit("reference-probe", probe, max_new_tokens=m)
    tokens = []
    while engine.has_work():
        before = len(tokens)
        tokens += [tok for rid, tok in engine.step()
                   if rid == "reference-probe" and tok is not None]
        if len(tokens) > before and len(tokens) > 1:   # a decode step ran
            chosen.append(np.asarray(engine.last_selection)[:, slot][:, None])
    return {"row": np.asarray(row.astype(jnp.float32)), "probe": probe,
            "probe_row": np.asarray(out[0].astype(jnp.float32)),
            "probe_tokens": tokens,
            "chosen": np.concatenate(chosen, axis=1)}


def _rows_against(rows, row, took, names, tols):
    """The readings of one request: the prefill row's errors and the streamed
    tokens' margin, each with its limit in units of the row's sigma."""
    import numpy as np

    sigma = float(rows[0].std())
    err = row - rows[0]
    m = len(took)
    picked = rows[np.arange(m), np.asarray(took)]
    best = rows.max(axis=-1)
    values = (np.abs(err).max(), np.sqrt(np.mean(err ** 2)),
              (best - picked).max())
    return ([{"name": name, "value": float(v), "limit": tol * sigma}
             for name, v, tol in zip(names, values, tols)], sigma,
            int((best == picked).sum()))


def compare(program, prompt, emitted, reference_params, config, shape):
    """The program's rows, tokens and selection against the plain reference
    over ``reference_params`` (the program's tree: the check passes the
    engine's own, the control the weights as they were before it rounded the
    engine's)."""
    import time

    import numpy as np

    from perfbench.manifest import resolve

    fwd = resolve(config["program"]["reference_forward"])
    ref_w = resolve(config["program"]["reference_weights"])(reference_params)
    tols = (REF_ROW_TOL_SIGMA, REF_RMS_TOL_SIGMA, REF_MARGIN_TOL_SIGMA)
    n, m = len(prompt), len(emitted)
    t0 = time.perf_counter()
    ref = fwd(ref_w, (list(prompt) + list(emitted))[:n + m - 1], shape,
              rows=np.arange(n - 1, n - 1 + m))
    rows = np.asarray(ref["logits"])
    t1 = time.perf_counter()
    readings, sigma, exact = _rows_against(
        rows, program["row"], emitted,
        ("prefill_max_abs_err", "prefill_rms_err", "max_margin"), tols)
    finite = bool(np.isfinite(rows).all() and np.isfinite(program["row"]).all())

    probe, took, chosen = (program["probe"], program["probe_tokens"],
                           program["chosen"])
    pn, pm = len(probe), len(took)
    want = probe_sizes(config, shape)[1]
    seq = (list(probe) + list(took))[:chosen.shape[1]]
    ref = fwd(ref_w, seq, shape, selection=chosen,
              rows=np.arange(pn - 1, len(seq)))
    prows = np.asarray(ref["logits"])
    t2 = time.perf_counter()
    more, psigma, pexact = _rows_against(
        prows, program["probe_row"], took[:len(prows)],
        ("probe_prefill_max_abs_err", "probe_prefill_rms_err",
         "probe_max_margin"), tols)
    under = np.asarray(ref["under"])
    own = np.sort(np.asarray(ref["own_selection"]), -1)
    past = own[..., 0] >= 0             # decisions of the sparse regime
    differ = (own != np.sort(chosen, -1)).any(-1) & past
    readings = [
        {"name": "selection_far_disagreements",
         "value": float((under > SELECT_TIE_TOL).sum()), "limit": 0.0},
        {"name": "probe_tokens_missing", "value": float(abs(want - pm)),
         "limit": 0.0}] + readings + more
    finite = finite and bool(np.isfinite(prows).all()
                             and np.isfinite(program["probe_row"]).all())
    return {
        "ok": all(r["value"] <= r["limit"] for r in readings),
        "finite": finite,
        "readings": readings,
        "notes": {"ref_logit_std": sigma, "probe_logit_std": psigma,
                  "exact_argmax": exact, "tokens": m,
                  "probe_len": pn, "probe_exact_argmax": pexact,
                  "selection_decisions": int(past.sum()),
                  "selection_disagreements": int(differ.sum()),
                  "selection_worst_under": float(under.max()),
                  "select_tie_tol": SELECT_TIE_TOL,
                  "reference_s": t1 - t0, "probe_reference_s": t2 - t1},
    }


def check(engine, prompt, emitted, config, shape) -> dict:
    return compare(program_out(engine, prompt, emitted, config, shape),
                   prompt, emitted, engine.params, config, shape)
