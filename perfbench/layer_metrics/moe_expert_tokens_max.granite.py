"""The most tokens one held expert got in a decode step (the largest over
the step's layers), averaged over the window's decode steps (a count):
``expert_tokens_max`` on the program's ``serve.engine.step`` rows. The mean
load is 64 slots x 10 picks / 72 experts = 8.9 tokens an expert; this is the
straggler, which a product grouped by expert pays for in its longest group
and a router's skew would raise."""

from perfbench import granite_bytes as gb


def read(ctx):
    return gb.per_step(ctx, "expert_tokens_max")
