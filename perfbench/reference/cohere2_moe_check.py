"""The reference check of the window / full attention family served by
``PagedEngine`` (``command-a-plus-serve1`` names it; the contract is in
``perfbench/reference/__init__.py``).

Two seeded requests through the engine's own programs, each against ONE pass
of the plain reference (float32, every query over all its keys under its
layer's mask, no ring, no pages, no chunks). As in ``nemotron_h_check`` and
``longcat_flash_check``, a routing decision at a near tie swaps a whole
expert, so the reference runs UNDER THE ROUTING THE TIMED PROGRAMS THEMSELVES
CHOSE at every position (its own scores for the imposed experts), and the
routing is held to a reading of its own.

**The contract's request** (200 tokens, 24 streamed). The prompt's routing
comes from the prefill program (the one ``_admit`` calls, which returns every
position's chosen experts beside its logits row); the decoded positions' from
the engine, which is given the request AGAIN here (idle and locked) and leaves
each step's chosen experts on the device (``last_routing``). 224 positions
stay inside one chunk, under the window and on four pages: this request sees
neither the window's second bound, nor the ring's wrap, nor a second block of
the full read.

(a) ``routing_far_disagreements``: at every layer and position the
    reference, fed the same upstream routing, makes its own choice; where
    that differs from the program's, the program's worst pick must lie within
    ``ROUTE_TIE_TOL`` (as a share of the reference's own cut-off score) of
    that cut-off. The count of decisions that lie farther, over both
    requests, is the reading; its limit is 0.
(b) ``prefill_max_abs_err`` / ``prefill_rms_err``: the prefill's row at the
    prompt's last position against the reference's.
(c) ``rerun_token_mismatches``: tokens of the re-run that differ from the
    streamed ones; limit 0 (same programs, same inputs).
(d) ``max_margin``: how far under the reference's best logit each of the 24
    streamed tokens' reference logit sits, over prompt + emitted.

**The probe** (``config["probe"]``: 6144 tokens, 8 tokens decoded). The
engine is idle and locked; the probe goes through the engine's own three-chunk
prefill (it crosses two chunk boundaries, and from position 4096 on a window
layer's query no longer sees the first keys: the window's second bound) and
then, submitted, through its admission (the full layer's 97 pages scattered,
each window layer's ring written from positions 2048 .. 6143, WRAPPED: position
``p`` at index ``p mod 4096``) and 8 of its own decode steps (the ring's write
and read at indices 2048 .. 2055, which evicts the positions the window has
passed; the full layer's read over seven blocks of 16 table columns, folded
into the slot's online softmax):

(e) ``probe_prefill_max_abs_err`` / ``probe_prefill_rms_err``: the prefill's
    last row against the reference under the program's routing.
(f) ``probe_max_margin``: each of the 8 tokens' margin against the reference
    over prompt + emitted. Every token after the first comes from the step
    over the ring and the pages the admission wrote: a ring written unwrapped,
    a read that keeps an evicted position, a window layer that rotates by
    the wrong position, or a full block dropped moves every later row
    (``tests/perfbench/test_commanda_check.py`` corrupts each and sees it
    fail), and shows in (a) at the decoded positions too.

This file, and no other that a benchmark run executes, reads what only this
family has: ``ray_tpu.models.cohere2_moe.prefill`` is public, the engine's
``last_routing`` is read as the hybrid and the latent family's is.
"""

from __future__ import annotations

#: Readings all these limits were set from (my chip runs, PR 43): twenty-five
#: sound runs on as many seeds (in ``cohere2_moe_control.py``'s calls and runs
#: of the cell) and the int8 control on eight seeds, some over 2**31.
#: Logits have sigma 0.992-1.008.
#:
#: (a) how far under the reference's own cut-off (the eighth sigmoid score of
#: 128, a number near 0.82) the program's worst pick may lie, as a share of
#: that cut-off, and still be a near tie. 4.7 % of the 25 496 decisions of a
#: run (1 111-1 265) disagree with the float32 reference's, all at near ties:
#: sound worst 0.0048-0.0085; the control 0.0219-0.0284, 13.5-14.8 % of its
#: decisions disagreeing. The worst of twenty-five thousand is an extreme
#: value (it rose from 0.0062 to 0.0085 between the fifth sound run and the
#: twenty-second, and stayed): the limit is the geometric middle of the sound runs'
#: largest and the control's smallest, 1.6 times from both, so the control
#: fails this reading too; it does not rest on it: it fails (b) and (e) on
#: every seed.
ROUTE_TIE_TOL = 0.0138
#: (b), (e) worst and root-mean-square |program - reference| over the 32768
#: logits of the prompt's last position, in units of that row's standard
#: deviation. Sound: RMS 0.0057-0.0064 on the contract's request, 0.0076-0.0084
#: on the probe (four layers; the 20-sublayer latent cell reads 0.008-0.010),
#: worst 0.022-0.031 / 0.030-0.040; control: RMS 0.0257-0.0281 / 0.0296-0.0329,
#: worst 0.107-0.120 / 0.114-0.140: 3.5-4.5 times apart (every product of
#: four layers quantised, the tied head not). Each limit is the geometric
#: middle of the sound runs' largest (the probe's) and the control's smallest
#: (the request's): 1.75 times from both (RMS), 1.64 times (worst). Both hold
#: the control out on every seed, on both requests.
REF_ROW_TOL_SIGMA = 0.0655
REF_RMS_TOL_SIGMA = 0.0148
#: (d), (f) the streamed tokens' margin, in the same unit. Sound: 0-0.0245
#: on the contract's 24 tokens (22-24 of them the reference's argmax),
#: 0-0.0186 on the probe's 8; the control 0-0.074 and 0-0.075 (it is not
#: held out by this reading, as no family's check's is: a flipped token at a
#: near tie says nothing of precision). A ring written unwrapped, a ring read
#: over indices no position has reached, a step that rotates by the wrong
#: position or a full read that drops a block reads far over it at toy widths
#: (``tests/perfbench/test_commanda_check.py``). A margin is at most the
#: row's error at two logits, so it stands under (b)'s worst: the limit is
#: twice the sound runs' largest.
REF_MARGIN_TOL_SIGMA = 0.05


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

    from ray_tpu.models import cohere2_moe as cohere

    row, _, routing = cohere.prefill(engine.params, prompt, engine.max_len,
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
