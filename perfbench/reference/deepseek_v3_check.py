"""The reference check of the DeepSeek-V3 family served by ``PagedEngine``
with its MTP module drafting (``gigachat3.1-702b-a36b-serve1`` names it; the
contract is in ``perfbench/reference/__init__.py``).

Two seeded requests through the engine's own programs, each against ONE pass
of the plain reference (float32, the EXPANDED attention form over the whole
committed sequence, the MTP module over the whole sequence). As in
``longcat_flash_check``, a routing decision at a near tie swaps a whole
expert, so the reference runs UNDER THE ROUTING THE TIMED PROGRAMS THEMSELVES
CHOSE at every position they decided (its own scores for the imposed experts),
and the routing is held to a reading of its own. Every call here says its
``temperature`` itself (``CHECK_TEMPERATURE``, with a seed): the deployment's
default does not reach the check, and drafts are accepted AND refused in it.

**The requests**: the contract's (200 tokens, 24 decoded) and the probe
(``config["probe"]``: 4500 tokens, three chunks, 16 decoded). Each goes
through the engine's own chunked prefill (which returns every position's
chosen experts beside its logits row) and then, submitted with an ``eos_id``
no token equals (so that each call lands the step it dispatched and the
step's logits can be read: ``last_logits``, ``last_draft_logits``,
``last_accepted``, ``last_routing``), through the engine's own two-row steps.
The contract's request steps alone in the engine; the probe steps with EVERY
OTHER SLOT LIVE (``_beside``: seeded fillers that say no ``eos_id``), so its
rows are read out of a full batch, and its second pass runs ahead as the
cell's window does: every slot held, up to ten steps in flight, the
positions handed from flight to flight on the device.

(a) ``prefill_max_abs_err`` / ``prefill_rms_err`` (and ``probe_``): the
    admission's row at the prompt's last position against the reference's;
    the probe's lies two chunk boundaries in.
(b) ``step_max_abs_err`` / ``step_rms_err`` (and ``probe_``): the worst, over
    the steps, of a step's ``l_0`` (and ``l_1`` where its draft was accepted:
    a refused draft's row is no position of the committed sequence) against
    the reference's row at that position of prompt + committed tokens.
(c) ``mtp_max_abs_err`` / ``mtp_rms_err`` (and ``probe_``): the worst, over
    the steps, of the MTP block's logits of the next draft against the
    reference's MTP row of the last committed pair.
(d) ``routing_far_disagreements``: where the reference, fed the same upstream
    routing, chooses other experts than the program did, the program's choice
    must lie within ``ROUTE_TIE_TOL`` of the reference's own cut-offs (the
    kept groups' and the chosen experts'); the count of decisions that lie
    farther, over both requests, decoder and MTP block, is the reading.
(e) ``bad_or_missing_tokens``: committed tokens outside the vocabulary slice,
    or a stream shorter than asked; ``runahead_token_mismatches``: the probe
    given again with the same seed and NO ``eos_id`` among the same fillers,
    so that the full engine runs ahead with positions handed on the device,
    must stream the same tokens in the same order.

This file, and no other that a benchmark run executes, reads what only this
family has: ``ray_tpu.models.deepseek_v3.prefill`` is public, the engine's
``last_*`` are read as the other families' ``last_routing`` is.
"""

from __future__ import annotations

#: what the check's own requests sample at, whatever the deployment's default
CHECK_TEMPERATURE = 1.0

#: Readings all these limits were set from (my chip runs, PR 60): seventeen
#: sound runs on seventeen seeds before the review (three in the control's
#: call, fourteen runs of the cell, a third of the seeds over 2**31), the
#: runs since (PERF.md section 6 has every reading), and the int8 control.
#: Logits have sigma 0.995-1.003. The probe's rows (4 500 positions of
#: context) read lower than the contract's (200) on both sides, so each
#: request has its own limits. What refuses a PR is a SOUND run that reads
#: false on a seed nobody has tried, so each limit stands nearer the control:
#: an rms limit (the numbers that decide the control: it must fail (a), (b)
#: and (c), and does so by these, 12-15 % clear on each of six seeds) at
#: 0.85-0.88 of the control's smallest reading, 2.2-2.7 times the sound runs'
#: largest; a
#: worst-row limit (the largest of 16 032 differences, of up to forty rows:
#: an extreme value, noisy by nature, kept because one column gone wrong by a
#: whole sigma moves an rms by a hundredth) at 0.9-0.98 of the control's
#: smallest, 1.9-2.6 times the sound runs' largest. Before the review each
#: was the geometric middle of the two readings, 1.44-1.8 times from both.
#:
#: (d) how far under the reference's own cut-off (a kept group's score, or
#: the chosen experts' score + bias) the program's choice may lie and still
#: be a near tie, as a share of that cut-off. 7 % of the
#: 23 688 decisions of a run disagree with the float32 reference's, all at
#: near ties: sound worst 0.005-0.009 over the seven runs since the reference
#: leaves a near-tied GROUP's experts out of the experts' cut-off (0.010-0.030
#: over the ten before, the control 0.020-0.033 with 19 % of its decisions
#: disagreeing). The limit is five times the sound runs' largest since;
#: the control does not rest on it: it fails (a), (b) and (c) on every seed.
ROUTE_TIE_TOL = 0.05
#: (a) worst and root-mean-square |program - reference| over the 16 032
#: logits of the prompt's last position, in units of that row's standard
#: deviation: (the contract's request, the probe). Sound: RMS
#: 0.0110-0.0123 / 0.0082-0.0091, worst 0.043-0.055 / 0.033-0.041; control
#: (six seeds): RMS 0.0351-0.0381 / 0.0233-0.0249, worst 0.136-0.154 /
#: 0.085-0.110 (2.7-3.3 times the sound readings, as weight-only int8 is).
REF_ROW_TOL_SIGMA = (0.133, 0.0765)
REF_RMS_TOL_SIGMA = (0.0305, 0.0198)
#: (b) the same over a step's rows, the worst of the steps. Sound: RMS
#: 0.0121-0.0126 / 0.0090-0.0096, worst 0.052-0.063 / 0.038-0.047; control:
#: RMS 0.0380-0.0401 / 0.0256-0.0261, worst 0.167-0.178 / 0.115-0.147.
STEP_ROW_TOL_SIGMA = (0.150, 0.105)
STEP_RMS_TOL_SIGMA = (0.0326, 0.0218)
#: (c) the same over the MTP block's rows, the worst of the steps. Sound:
#: RMS 0.0124-0.0132 / 0.0086-0.0090, worst 0.053-0.062 / 0.037-0.048; control:
#: RMS 0.0403-0.0427 / 0.0269-0.0284, worst 0.177-0.224 / 0.117-0.133.
MTP_ROW_TOL_SIGMA = (0.163, 0.105)
MTP_RMS_TOL_SIGMA = (0.0353, 0.0229)


def _probe(config: dict, shape: dict) -> dict:
    toy = shape["hidden_size"] != config["hidden_size"]
    return (config["rehearsal"] if toy else config)["probe"]


def probe_sizes(config: dict, shape: dict):
    """(prompt length, tokens decoded) of the probe, as run."""
    p = _probe(config, shape)
    return int(p["prompt_len"]), int(p["new_tokens"])


def probe_prompt(prompt, n: int, vocab: int):
    """The probe's token ids: seeded by the contract's prompt, which the
    run's seed drew."""
    import random

    rng = random.Random((prompt[0] << 40) | (prompt[1] << 20) | prompt[2])
    return [rng.randrange(vocab) for _ in range(n)]


def _seed_of(prompt) -> int:
    return (prompt[0] * 1_000_003 + prompt[1]) % (2 ** 31)


def _stream(engine, rid, prompt, new, eos_id):
    """-> (the tokens streamed for ``rid``, per landed step what the engine
    published: (logits [2, V] float32, accepted, the MTP logits [V], the
    chosen experts [expert layers, 2, k])). Returns when ``rid`` ends,
    whatever else the engine holds."""
    import numpy as np

    slot = engine.slots.index(None)     # where ``_admit_one`` will put it
    engine.submit(rid, prompt, max_new_tokens=new, eos_id=eos_id,
                  temperature=CHECK_TEMPERATURE, seed=_seed_of(prompt))
    tokens, steps, ended = [], [], False
    while not ended:
        for r, tok in engine.step():
            if r == rid:
                ended = tok is None
                tokens += [] if ended else [tok]
        if eos_id is not None and engine._landed:
            f32 = lambda a: np.asarray(a, np.float32)   # noqa: E731
            steps.append((
                f32(engine.last_logits[slot]),
                bool(engine.last_accepted[slot]),
                f32(engine.last_draft_logits[slot]),
                np.asarray(engine.last_routing)[:, 2 * slot:2 * slot + 2]))
    return tokens, steps


def _beside(engine, prompt, new, count):
    """Fill ``count`` slots (the configuration's ``probe.beside``: every slot
    but one at the cell's size, so that the run-ahead's cap is its ten; a few
    in a rehearsal) with seeded requests that outlast two passes of ``new``
    tokens (a step commits at most two a slot) and say no ``eos_id``:
    admitted by the one call, decoding from the next."""
    count = min(count, engine.S - 1)
    for i in range(count):
        engine.submit(f"reference-beside-{i}",
                      probe_prompt(prompt[i:i + 3], 96,
                                   int(engine.cfg.vocab_size)),
                      max_new_tokens=4 * new + 8,
                      temperature=CHECK_TEMPERATURE,
                      seed=(_seed_of(prompt) + 1 + i) % (2 ** 31))
    engine.step()
    return count


def _through_engine(engine, rid, prompt, new, beside=None):
    """One request through the engine's own prefill and steps: alone, or (the
    probe) with ``beside`` other slots live, and then once more with no
    ``eos_id`` so that the engine runs ahead."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import deepseek_v3 as deepseek

    row, _, _, routing = deepseek.prefill(
        engine.params, prompt, engine.max_len, engine.cfg, keep_routing=True)
    never = int(engine.cfg.vocab_size)      # an eos no token equals
    rerun = None
    if beside is not None:
        beside = _beside(engine, prompt, new, beside)
    tokens, steps = _stream(engine, rid, prompt, new, never)
    if beside is not None:
        rerun = _stream(engine, rid + "-ahead", prompt, new, None)[0]
        while engine.has_work():            # the fillers' last tokens
            engine.step()
    return {"row": np.asarray(row.astype(jnp.float32)), "tokens": tokens,
            "steps": steps, "routing": routing, "rerun": rerun,
            "beside": beside}


def program_out(engine, prompt, emitted, config, shape):
    """What the engine's own programs produce for the contract's request
    (given again, at the check's own temperature) and for the probe."""
    n, m = probe_sizes(config, shape)
    probe = probe_prompt(prompt, n, shape["vocab_size"])
    return {"request": _through_engine(engine, "reference-check", prompt,
                                       len(emitted)),
            "probe": probe,
            "probed": _through_engine(
                engine, "reference-probe", probe, m,
                int(_probe(config, shape)["beside"]))}


def _against(fwd, ref_w, shape, prompt, got, prefix: str):
    """One request's readings and what the reference says of the imposed
    routing."""
    import numpy as np

    n, tokens, steps = len(prompt), got["tokens"], got["steps"]
    seq = list(prompt) + list(tokens)
    L = len(seq)
    layers = got["routing"].shape[0]
    k = got["routing"].shape[2]
    routing = np.zeros((layers, L, k), np.int32)
    imposed = np.zeros((layers, L), bool)
    # the prompt: every decoder position, and the MTP rows whose follower the
    # chunk knew (row n - 1 is the first draft's, which keeps no routing)
    routing[:, :n] = got["routing"]
    imposed[:-1, :n] = True
    imposed[-1, :n - 1] = True
    # the steps: row 0 at the slot's position, row 1 where it was accepted
    at, main_rows, mtp_rows, where = n, [], [], []
    for logits, accepted, q_logits, chosen in steps:
        for r in range(1 + accepted):
            if at + r < L:
                routing[:, at + r] = chosen[:, r]
                imposed[:-1, at + r] = True
                # the MTP row needs its follower, a committed token
                imposed[-1, at + r] = at + r + 1 < L
                main_rows.append((at + r, logits[r]))
        last = at + accepted
        if last + 1 < L:
            mtp_rows.append((last, q_logits))
        at += 1 + accepted
    ref = fwd(ref_w, seq, shape, routing=routing, imposed=imposed,
              rows=np.array([n - 1] + [p for p, _ in main_rows]),
              mtp_rows=np.array([p for p, _ in mtp_rows] or [0]))
    rows = np.asarray(ref["logits"])
    sigma = float(rows[0].std())

    def errs(mine, theirs):
        e = np.asarray(mine) - theirs
        return float(np.abs(e).max()), float(np.sqrt(np.mean(e ** 2)))

    a = errs(got["row"], rows[0])
    b = [errs(l, rows[1 + i]) for i, (_, l) in enumerate(main_rows)] \
        or [(0.0, 0.0)]
    mtp = np.asarray(ref["mtp_logits"])
    c = [errs(q, mtp[i]) for i, (_, q) in enumerate(mtp_rows)] or [(0.0, 0.0)]
    values = (a[0], a[1], max(x[0] for x in b), max(x[1] for x in b),
              max(x[0] for x in c), max(x[1] for x in c))
    names = ("prefill_max_abs_err", "prefill_rms_err", "step_max_abs_err",
             "step_rms_err", "mtp_max_abs_err", "mtp_rms_err")
    which = 1 if prefix else 0      # the probe's limits, or the contract's
    tols = [t[which] for t in (
        REF_ROW_TOL_SIGMA, REF_RMS_TOL_SIGMA, STEP_ROW_TOL_SIGMA,
        STEP_RMS_TOL_SIGMA, MTP_ROW_TOL_SIGMA, MTP_RMS_TOL_SIGMA)]
    under = np.concatenate([np.asarray(u)[m[:len(u)]] for u, m in
                            zip(ref["under"], imposed)])
    differ = np.concatenate([
        (np.sort(np.asarray(o), -1) != np.sort(r[:len(o)], -1)).any(-1)[
            m[:len(o)]]
        for o, r, m in zip(ref["own_routing"], routing, imposed)])
    vocab = int(shape["vocab_size"])
    return {
        "readings": [{"name": prefix + nm, "value": v, "limit": tol * sigma}
                     for nm, v, tol in zip(names, values, tols)],
        "sigma": sigma, "under": under, "differ": differ,
        "accepted": sum(s[1] for s in steps), "steps": len(steps),
        "compared": len(main_rows), "mtp_compared": len(mtp_rows),
        "bad": sum(not 0 <= t < vocab for t in tokens),
        "finite": bool(np.isfinite(rows).all() and np.isfinite(mtp).all()
                       and np.isfinite(got["row"]).all())}


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
    t0 = time.perf_counter()
    a = _against(fwd, ref_w, shape, prompt, program["request"], "")
    t1 = time.perf_counter()
    b = _against(fwd, ref_w, shape, program["probe"], program["probed"],
                 "probe_")
    t2 = time.perf_counter()
    want = (len(emitted), probe_sizes(config, shape)[1])
    took = (program["request"]["tokens"], program["probed"]["tokens"])
    rerun = program["probed"]["rerun"]
    vocab = int(shape["vocab_size"])
    under = np.concatenate([a["under"], b["under"]])
    differ = np.concatenate([a["differ"], b["differ"]])
    readings = [
        {"name": "routing_far_disagreements",
         "value": float((under > ROUTE_TIE_TOL).sum()), "limit": 0.0},
        {"name": "bad_or_missing_tokens",
         "value": float(a["bad"] + b["bad"]
                        + sum(not 0 <= t < vocab for t in emitted)
                        + sum(abs(w - len(t)) for w, t in zip(want, took))),
         "limit": 0.0},
        {"name": "runahead_token_mismatches",
         "value": float(sum(x != y for x, y in zip(rerun, took[1]))
                        + abs(len(rerun) - len(took[1]))), "limit": 0.0},
    ] + a["readings"] + b["readings"]
    return {
        "ok": all(r["value"] <= r["limit"] for r in readings),
        "finite": a["finite"] and b["finite"],
        "readings": readings,
        "notes": {"ref_logit_std": a["sigma"], "probe_logit_std": b["sigma"],
                  "steps": a["steps"] + b["steps"],
                  "drafts_accepted": a["accepted"] + b["accepted"],
                  "step_rows_compared": a["compared"] + b["compared"],
                  "mtp_rows_compared": a["mtp_compared"] + b["mtp_compared"],
                  "probe_len": len(program["probe"]),
                  "probe_beside": program["probed"]["beside"],
                  "routing_decisions": int(differ.size),
                  "routing_disagreements": int(differ.sum()),
                  "routing_worst_under": float(under.max()),
                  "route_tie_tol": ROUTE_TIE_TOL,
                  "reference_s": t1 - t0, "probe_reference_s": t2 - t1},
    }


def check(engine, prompt, emitted, config, shape) -> dict:
    return compare(program_out(engine, prompt, emitted, config, shape),
                   prompt, emitted, engine.params, config, shape)
