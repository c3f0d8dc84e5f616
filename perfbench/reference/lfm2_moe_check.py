"""The reference check of the gated-short-convolution / attention family
served by ``PagedEngine`` (``lfm2-24b-a2b-serve1`` names it; the contract is
in ``perfbench/reference/__init__.py``).

Two seeded requests through the engine's own programs, each against ONE pass
of the plain reference (float32, a convolution over the whole sequence, every
query over all its keys, no tail, no pages, no chunks). As in
``nemotron_h_check``, ``longcat_flash_check`` and ``cohere2_moe_check``, a
routing decision at a near tie swaps a whole expert, so the reference runs
UNDER THE ROUTING THE TIMED PROGRAMS THEMSELVES CHOSE at every position (its
own scores for the imposed experts), and the routing is held to a reading of
its own.

**The contract's request** (200 tokens, 24 streamed). The prompt's routing
comes from the prefill program (the one ``_admit`` calls, which returns every
position's chosen experts beside its logits row); the decoded positions' from
the engine, which is given the request AGAIN here (idle and locked) and leaves
each step's chosen experts on the device (``last_routing``). 224 positions
stay inside one chunk: this request sees no chunk boundary.

(a) ``routing_far_disagreements``: at every expert layer and position the
    reference, fed the same upstream routing, makes its own choice; where
    that differs from the program's, the program's worst pick must lie within
    ``ROUTE_TIE_TOL`` (as a share of the reference's own cut-off, score +
    bias) of that cut-off. The count of decisions that lie farther, over both
    requests, is the reading; its limit is 0.
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
position 2048: the eight conv layers' tails (the second chunk's left edge:
positions 2046 and 2047 of ``z``) and the two attention layers' K/V rows of
the first chunk. Then, submitted, it goes through its admission (141 pages of
each pooled layer scattered, the eight tails at position 2248 written into
its slot) and 8 of its own decode steps (a conv layer's step over the tail
the admission wrote, the blocked read over three blocks of 48 table columns):

(e) ``probe_prefill_max_abs_err`` / ``probe_prefill_rms_err``: the prefill's
    last row against the reference under the program's routing. A second
    chunk that starts from a zero tail, or from the first chunk's tail at the
    wrong rows, or a prefill that loses the first chunk's K/V moves this row
    (``tests/perfbench/test_lfm2_check.py`` corrupts each and sees it fail).
(f) ``probe_max_margin``: each of the 8 tokens' margin against the reference
    over prompt + emitted. Every token after the first comes from the step
    over the tails and the pages the admission wrote.

This file, and no other that a benchmark run executes, reads what only this
family has: ``ray_tpu.models.lfm2_moe.prefill`` is public, the engine's
``last_routing`` is read as the other expert families' is.
"""

from __future__ import annotations

#: Readings all these limits were set from (my chip runs, PR 52; PERF.md
#: section 4 has the runs): the sound engine on seventeen seeds (three in
#: ``lfm2_moe_control.py``'s call, fourteen runs of the cell) and the int8
#: control on three seeds, one over 2**31; fourteen of the seventeen are over
#: 2**31. Logits have sigma 0.994-1.009.
#:
#: **Why the sound readings are two to three times the other families'** (RMS
#: 0.027-0.031 sigma where the latent and the window / full cells read
#: 0.006-0.010 and the dense one 0.013): the gated short convolution. Its
#: output is a product of THREE bfloat16 factors (``C * conv(B * X)``), each
#: carrying its own projection's rounding and none squashed by a softmax or a
#: silu, so a conv operator hands on about 2.5 times the relative error of
#: an attention operator. By ablation at 512 wide on the CPU in bfloat16, ten
#: layers, the same seed: every operator a convolution RMS 0.029, the
#: published 8 + 2 mix 0.026, every operator attention 0.011; two layers
#: 0.009, four 0.012: it grows with the conv layers and is no fault of the
#: cache (float32 toy: 2e-7 through chunks, pages and tails,
#: ``tests/test_lfm2_moe.py``).
#:
#: (a) how far under the reference's own cut-off (the fourth of 64 sigmoid
#: scores + bias, a number near 0.73) the program's worst pick may lie, as a
#: share of that cut-off, and still be a near tie. 8 % of the 19 824
#: decisions of a run (1 502-1 666) disagree with the float32 reference's,
#: all at near ties: sound worst 0.0187-0.0347 (twelve of seventeen under
#: 0.027); the control 0.0798-0.0931, 25 % of its decisions disagreeing and
#: 750-893 of them over the first limit tried (0.0475). The worst of twenty
#: thousand is an extreme value (it rose from 0.0283 over the first four
#: runs to 0.0347 over seventeen): the limit is the geometric middle of the
#: sound runs' largest and the control's smallest, 1.52 times from both.
#: The control does not rest on it: it fails (b) and (e) on every seed.
ROUTE_TIE_TOL = 0.0526
#: (b), (e) worst and root-mean-square |program - reference| over the 65536
#: logits of the prompt's last position, in units of that row's standard
#: deviation. Sound: RMS 0.0265-0.0319 on the contract's request, 0.0247-
#: 0.0308 on the probe, worst 0.114-0.145 / 0.105-0.158; control: RMS
#: 0.0929-0.1025 / 0.0921-0.0995, worst 0.388-0.474 / 0.391-0.448: 3.2 times
#: apart (every projection of ten layers quantised, the tied head and the
#: conv taps not). Each limit is the geometric middle of the sound runs'
#: largest and the control's smallest: 1.70 times from both (RMS), 1.57 times
#: (worst). Both hold the control out on every seed, on both requests.
REF_ROW_TOL_SIGMA = 0.2476
REF_RMS_TOL_SIGMA = 0.0542
#: (d), (f) the streamed tokens' margin, in the same unit. Sound: 0-0.0558 on
#: the contract's 24 tokens (21-24 of them the reference's argmax), 0-0.0767
#: on the probe's 8 (5-8 of 8); the control 0.058-0.273 and 0.080-0.337 (it
#: is not held out by this reading on every seed, as no family's check's is:
#: a flipped token at a near tie says nothing of precision). A second chunk
#: without the first's K/V, or a tail taken at the chunk's padded end, reads
#: far over it at toy widths (``tests/perfbench/test_lfm2_check.py``). A
#: margin is at most the row's error at two logits, so it stands under twice
#: (b)'s worst: the limit is twice the sound runs' largest (the first limit,
#: 0.08 from four runs' 0.0253, stood 4 % over the seventeen runs' largest).
REF_MARGIN_TOL_SIGMA = 0.155


# what a check of two requests under an imposed routing needs and no family
# owns: the probe's sizes and token ids, and one request's readings
from perfbench.reference.longcat_flash_check import (  # noqa: E402
    _against, probe_prompt, probe_sizes)


def _through_engine(engine, rid, prompt, new):
    """The request through the engine's own prefill and decode steps: (the
    prefill's float32 row, the streamed tokens, the chosen experts [expert
    layers, len(prompt) + tokens - 1, k] of the prefill program and of the
    steps)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import lfm2_moe as lfm2

    row, _, _, routing = lfm2.prefill(engine.params, prompt, engine.max_len,
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
