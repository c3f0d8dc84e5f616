"""The reference check of the hybrid Mamba-2 / attention / routed-expert
family served by ``PagedEngine`` (``nemotron-3-nano-30b-a3b-serve1`` names
it; the contract is in ``perfbench/reference/__init__.py``).

One seeded request through the engine's own programs, against ONE pass of the
plain reference over prompt + emitted tokens. Why the dense check cannot
serve: a routing decision at a near tie swaps a whole expert, and with 16 of
128 experts held a swapped-in expert is most of a row's routed part. On the
chip a tenth of all routing decisions differ from a free-running reference's
and such a row's logits move by up to 1.8 sigma; even two compilations of the
program's own decode arithmetic (32 slots and one) route 5-10 of 24 tokens
apart (PERF.md section 6, PR 28). So the reference runs UNDER THE ROUTING
THE TIMED PROGRAMS THEMSELVES CHOSE at every position (its own scores for the
imposed experts), and the routing is held to a reading of its own.

Where that routing comes from: the prompt's from the prefill program (the one
``_admit`` calls, at the bucket it pads to, which returns the chosen experts
beside its logits row); the decoded positions' from the engine, which is
given the request AGAIN here (idle and locked, so it lands in the same slot)
and leaves each step's chosen experts on the device (``last_routing``). The
re-run must stream the very tokens the first run did: reading (c).

(a) ``routing_far_disagreements``: at every expert block and position the
    reference, fed the same upstream routing, makes its own choice; where
    that differs from the program's, the program's worst pick must lie
    within ``ROUTE_TIE_TOL`` of the reference's own cut-off in score + bias
    (for one swap at the edge that IS the reference's gap between its last
    choice and the next). The count of disagreements that lie farther is
    the reading; its limit is 0. (Issue 28 asked for a free-running
    reference here; a free-running one carries every earlier swap in its
    hidden state, so its later disagreements need not sit at ties at all.
    Imposing the upstream routing isolates each decision.)
(b) ``prefill_max_abs_err`` / ``prefill_rms_err``: the prefill's row at the
    prompt's last position against the reference's.
(c) ``rerun_token_mismatches``: tokens of the re-run that differ from the
    streamed ones; limit 0 (same programs, same inputs, same slot).
(d) ``max_margin``: how far under the reference's best logit each of the 24
    streamed tokens' reference logit sits, over prompt + emitted. The first
    token comes from the prefill, every later one from the timed decode step
    over the state the admission wrote into the slot and the pages it
    scattered: a wrong state hand-over, convolution tail or recurrence moves
    every later row, and shows here where it flips a token and in (a) at
    the decoded positions, whose routing then lies far from the reference's
    (``tests/perfbench/test_nemotron_check.py`` corrupts the state write and
    sees both; row-by-row agreement of the decode LOGITS with
    the reference is a tier-1 test in float32,
    ``tests/test_nemotron_h.py``, since the engine hands out tokens only).

This file, and no other that a benchmark run executes, imports a private name
of the program: ``ray_tpu.models.nemotron_h._hybrid_prefill`` (the jitted
prefill ``PagedEngine._prefill`` calls: same function object, same compiled
program at the same bucket); nothing public returns its logits row or its
routing.
"""

from __future__ import annotations

#: (a) how far under the reference's own cut-off (sigmoid score + bias, a
#: number in 0..1) the program's worst pick may lie and still be a near tie.
#: Readings (my chip runs, PR 28): 75 sound runs on 75 seeds 0.0078-0.0178
#: (10-12 % of the 4 600-5 129 decisions of a run disagree, all that near;
#: only the last 14 runs include the decoded positions, and hold the
#: largest); the int8 control on 44 seeds 0.0251-0.0484, with 14-32 decisions
#: of a run over 0.02. The worst of thousands is an extreme value: the limit
#: stands 1.4 times over the sound runs' largest and still under the smallest
#: of the 14 controls run on today's weights (0.0276). The control does not
#: rest on it: it fails (b) by a wide distance on every seed.
ROUTE_TIE_TOL = 0.025
#: (b) worst and root-mean-square |program - reference| over the 16384 logits
#: of the prompt's last position, in units of that row's standard deviation.
#: Readings (the same runs): sound 0.0756-0.0981 and 0.0192-0.0225 (52 blocks:
#: about 1.5 times the dense cell's 16-layer 0.013-0.014), control
#: 0.1845-0.276 and 0.0493-0.0619: 2.2-2.6 times apart, as weight-only int8
#: is (PERF.md section 6, PR 27). Each limit is the geometric middle of the
#: sound runs' largest and the control's smallest: 1.4-1.5 times from both.
REF_ROW_TOL_SIGMA = 0.135
REF_RMS_TOL_SIGMA = 0.033
#: (d) readings under the engine's own routing (14 sound seeds): 0-0.060
#: sigma, 21-24 of 24 tokens the reference's argmax; the control 0.016-0.181
#: (it is not held out by this reading, as the dense check's is not). Against
#: a free-running reference the same sound engine read 0.05-1.85. A zeroed
#: convolution tail reads 0.56-2.4 at toy widths. The limit is 2.5 times the
#: sound runs' largest.
REF_MARGIN_TOL_SIGMA = 0.15


def program_out(engine, prompt, emitted):
    """(float32 logits row of the prefill at the prompt's last position; the
    tokens the engine streams when given the request again; the chosen
    experts [expert blocks, len(prompt) + len(emitted) - 1, k] of the prefill
    program and of the engine's own decode steps)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.nemotron_h import _hybrid_prefill

    n = len(prompt)
    pad = next(b for b in list(engine._prefill_buckets) + [engine.max_len]
               if b >= n)
    padded = jnp.asarray(prompt + [0] * (pad - n), jnp.int32)
    first, _, _, routing = _hybrid_prefill(
        engine.params, padded, n, engine.max_len, engine.cfg, pad)
    routes = [np.asarray(routing)[:, :n]]
    slot = engine.slots.index(None)     # where ``_admit_one`` will put it
    engine.submit("reference-check", prompt, max_new_tokens=len(emitted))
    tokens = []
    while engine.has_work():
        before = len(tokens)
        tokens += [tok for rid, tok in engine.step()
                   if rid == "reference-check" and tok is not None]
        if len(tokens) > before and len(tokens) > 1:   # a decode step ran
            routes.append(np.asarray(engine.last_routing)[:, slot][:, None])
    return (np.asarray(first.astype(jnp.float32)), tokens,
            np.concatenate(routes, axis=1))


def compare(program, prompt, emitted, reference_params, config, shape):
    """The program's row, re-run tokens and routing, and the streamed tokens,
    against the plain reference over ``reference_params`` (the program's
    tree: the check passes the engine's own, the control the weights as they
    were before it rounded the engine's)."""
    import numpy as np

    from perfbench.manifest import resolve

    row, rerun, routing = program
    fwd = resolve(config["program"]["reference_forward"])
    to_ref = resolve(config["program"]["reference_weights"])
    seq = list(prompt) + list(emitted)
    n, m = len(prompt), len(emitted)
    ref = fwd(to_ref(reference_params), seq, shape, routing=routing)
    ref_rows = np.asarray(ref["logits"])[n - 1:n - 1 + m]
    under = np.asarray(ref["under"])[:, :routing.shape[1]]
    own = np.sort(np.asarray(ref["own_routing"])[:, :routing.shape[1]], -1)
    differ = (own != np.sort(routing, axis=-1)).any(axis=-1)
    sigma = float(ref_rows[0].std())
    err = row - ref_rows[0]
    took = np.asarray(emitted)
    picked = ref_rows[np.arange(m), took]
    best = ref_rows.max(axis=-1)
    mismatches = sum(a != b for a, b in zip(rerun, emitted)) \
        + abs(len(rerun) - m)
    readings = [
        {"name": "routing_far_disagreements",
         "value": float((under > ROUTE_TIE_TOL).sum()), "limit": 0.0},
        {"name": "rerun_token_mismatches", "value": float(mismatches),
         "limit": 0.0}] + [
        {"name": name, "value": float(value), "limit": tol * sigma}
        for name, value, tol in (
            ("prefill_max_abs_err", np.abs(err).max(), REF_ROW_TOL_SIGMA),
            ("prefill_rms_err", np.sqrt(np.mean(err ** 2)),
             REF_RMS_TOL_SIGMA),
            ("max_margin", (best - picked).max(), REF_MARGIN_TOL_SIGMA))]
    return {
        "ok": all(r["value"] <= r["limit"] for r in readings),
        "finite": bool(np.isfinite(ref_rows).all()
                       and np.isfinite(row).all()),
        "readings": readings,
        "notes": {"ref_logit_std": sigma,
                  "routing_decisions": int(differ.size),
                  "routing_disagreements": int(differ.sum()),
                  "routing_worst_under": float(under.max()),
                  "route_tie_tol": ROUTE_TIE_TOL,
                  "exact_argmax": int((best == picked).sum()),
                  "tokens": m},
    }


def check(engine, prompt, emitted, config, shape) -> dict:
    return compare(program_out(engine, prompt, emitted), prompt, emitted,
                   engine.params, config, shape)
