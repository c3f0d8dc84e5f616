"""The reference check of the Granite-MoE-hybrid family served by
``PagedEngine`` (``granite-4.0-h-small-serve1`` names it; the contract is in
``perfbench/reference/__init__.py``).

Two seeded requests through the engine's own programs, each against ONE pass
of the plain reference (float32, the recurrence position by position from a
zero state, a convolution over the whole sequence, every query over all its
keys: no chunks, no carried state, no tails, no pages). As in
``nemotron_h_check``, ``longcat_flash_check``, ``cohere2_moe_check`` and
``lfm2_moe_check``, a routing decision at a near tie swaps a whole expert, so
the reference runs UNDER THE ROUTING THE TIMED PROGRAMS THEMSELVES CHOSE at
every position (its own logits for the imposed experts), and the routing is
held to a reading of its own.

**The contract's request** (200 tokens, 24 streamed). The prompt's routing
comes from the prefill program (the one ``_admit`` calls, which returns every
position's chosen experts beside its logits row); the decoded positions' from
the engine, which is given the request AGAIN here (idle and locked) and leaves
each step's chosen experts on the device (``last_routing``). 224 positions
stay inside one chunk: this request sees no chunk boundary.

(a) ``routing_far_disagreements``: at every layer and position the reference,
    fed the same upstream routing, makes its own choice; where that differs
    from the program's, the program's worst pick must lie within
    ``ROUTE_TIE_TOL`` of the reference's own cut-off (the TENTH of the 72
    logits), in units of that position's spread of logits over the experts.
    The count of decisions that lie farther, over both requests, is the
    reading; its limit is 0.
(b) ``prefill_max_abs_err`` / ``prefill_rms_err``: the prefill's row at the
    prompt's last position against the reference's.
(c) ``rerun_token_mismatches``: tokens of the re-run that differ from the
    streamed ones; limit 0 (same programs, same inputs).
(d) ``max_margin``: how far under the reference's best logit each of the 24
    streamed tokens' reference logit sits, over prompt + emitted.

**The probe** (``config["probe"]``: 2048 + 200 = 2248 tokens, 8 tokens
decoded): the ONE reading more than the contract's request. The engine is
idle and locked; the probe goes through the engine's own two-chunk prefill,
so that its first-token logits depend on what crossed the chunk boundary at
position 2048: the nine Mamba layers' SSM states (the second chunk's ``h0``),
their nine tails (its convolutions' left edge: positions 2045-2047 of
``xBC``) and the attention layer's K/V rows of the first chunk. Then,
submitted, it goes through its admission (141 pages of the pooled layer
scattered, the nine states and tails at position 2248 written into its slot)
and 8 of its own decode steps (the recurrence from the state the admission
wrote, the blocked read over the pages):

(e) ``probe_prefill_max_abs_err`` / ``probe_prefill_rms_err``: the prefill's
    last row against the reference under the program's routing. A second
    chunk that starts from a zero state or a zero tail, or a prefill that
    loses the first chunk's K/V, moves this row or the routing at the edge
    (``tests/perfbench/test_granite_check.py`` corrupts each and sees it
    fail).
(f) ``probe_max_margin``: each of the 8 tokens' margin against the reference
    over prompt + emitted. Every token after the first comes from the step
    over the state, the tails and the pages the admission wrote.

**What this check does not see: a bfloat16 SSM state.**
``granite_moe_hybrid_control.py`` runs it as a second control (``--state
bfloat16``: the slots' SSM state kept in bfloat16 between steps, the
recurrence's arithmetic float32 as it is); what it read on the chip is
written beside the limits below. Readings (b) and (e) take their rows from
``granite.prefill`` called here, which never reads ``engine.ssm``: the
control cannot move them by construction. The probe decodes 8 tokens and the
contract's request 24 over a state the admission wrote in float32, so a
state rounded at every step has at most 24 steps to drift in, and the only
readings that see decode steps are the margins; like ``dense_check`` with
int8 pages, a check that sees the state's precision needs the decode steps'
logits (the probed slot's row after its k-th step against the reference at
that position) over tens to hundreds of steps. Until it reads them, NO
CHANGE OF THE STATE'S DTYPE MAY CLAIM A GAIN in a cell this check decides
(PERF.md sections 3 and 7).

This file, and no other that a benchmark run executes, reads what only this
family has: ``ray_tpu.models.granite_moe_hybrid.prefill`` is public, the
engine's ``last_routing`` is read as the other expert families' is.
"""

from __future__ import annotations

#: Readings all these limits were set from (my chip runs, PR 56; PERF.md
#: section 6 has the runs): the sound engine on seventeen seeds (three in
#: ``granite_moe_hybrid_control.py``'s calls, the rest runs of the cell) and
#: the int8 control on three seeds, one over 2**31. Logits have sigma
#: 0.0052 (the embedding enters the stream at 1 / 64 an entry so that the
#: tied head does not hand a token its own row: the configuration's
#: ``assumed``); every limit is in units of the row's own sigma.
#:
#: (a) how far under the reference's own cut-off (the TENTH of the 72 router
#: logits) the program's worst pick may lie, in units of that position's
#: spread of logits over the 72 experts, and still be a near tie. 12-13 % of
#: the 24 780 decisions of a run (3 037-3 298) disagree with the float32
#: reference's, all at near ties: sound worst 0.0839-0.1358; the
#: control 0.2913-0.2979, 36 % of its decisions disagreeing (8 832-8 911).
#: The limit is the geometric middle of the sound runs' largest and the
#: control's smallest, 1.46 times from both. The control does not rest on
#: it: it fails (b) and (e) on every seed.
ROUTE_TIE_TOL = 0.1989
#: (b), (e) worst and root-mean-square |program - reference| over the 50176
#: logits of the prompt's last position, in units of that row's standard
#: deviation. Sound: RMS 0.0212-0.0250 on the contract's request,
#: 0.0192-0.0269 on the probe, worst 0.0846-0.1173 / 0.0846-0.1250;
#: control: RMS 0.0689-0.0739 / 0.0678-0.0728, worst 0.292-0.335 / 0.296-
#: 0.324: 2.5 times apart (every projection of ten layers quantised, the
#: tied head, the convolutions, the routers and the per-head vectors not).
#: Each limit is the geometric middle of the sound runs' largest and the
#: control's smallest: 1.59 times from both (RMS), 1.53 times (worst). Both
#: hold the control out on every seed, on both requests.
REF_ROW_TOL_SIGMA = 0.1911
REF_RMS_TOL_SIGMA = 0.0427
#: (d), (f) the streamed tokens' margin, in the same unit. Sound: 0-0.0981
#: on the contract's 24 tokens (21-24 of them the reference's argmax),
#: 0-0.0481 on the probe's 8 (7-8 of 8); the control 0.010-0.173
#: and 0.049-0.096 (it is not held out by this reading on every seed, as no
#: family's check's is: a flipped token at a near tie says nothing of
#: precision). A second chunk without the first's K/V reads far over it at
#: toy widths (``tests/perfbench/test_granite_check.py``). A margin is at
#: most the row's error at two logits, so it stands under twice (b)'s worst:
#: the limit is twice the sound runs' largest (the first, 0.12 set when four
#: runs had read at most 0.0194, stood only a fifth over the seventeen runs'
#: largest and was reset before any run was refused).
REF_MARGIN_TOL_SIGMA = 0.196
#:
#: The second control (``--state bfloat16``, two seeds): every reading of
#: the engine whose SSM state is rounded to bfloat16 after each step EQUALS
#: the sound engine's (the same tokens, the same routing, RMS 0.0215-0.0229):
#: the check does not see the state's dtype (the docstring above says why).


# what a check of two requests under an imposed routing needs and no family
# owns: the probe's sizes and token ids, and one request's readings
from perfbench.reference.longcat_flash_check import (  # noqa: E402
    _against, probe_prompt, probe_sizes)


def _through_engine(engine, rid, prompt, new):
    """The request through the engine's own prefill and decode steps: (the
    prefill's float32 row, the streamed tokens, the chosen experts [layers,
    len(prompt) + tokens - 1, k] of the prefill program and of the steps)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import granite_moe_hybrid as granite

    row, _, _, routing = granite.prefill(
        engine.params, prompt, engine.max_len, engine.cfg, keep_routing=True)
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
