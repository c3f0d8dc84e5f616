"""The most tokens one expert got in a decode step (the largest over the
step's expert layers), averaged over the window's decode steps (a count):
``expert_tokens_max`` on the program's ``serve.engine.step`` rows. The mean
load is 64 slots x 4 picks / 64 experts = 4 tokens an expert; this is the
straggler of 64 small experts, which a product grouped by expert pays for in
its longest group and a router's skew would raise."""

from perfbench import lfm2_bytes as lb


def read(ctx):
    return lb.per_step(ctx, "expert_tokens_max")
