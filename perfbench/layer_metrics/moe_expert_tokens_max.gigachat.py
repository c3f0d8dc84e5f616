"""The most rows one held expert got in a step (the largest over the step's
expert layers), averaged over the window's steps (a count):
``expert_tokens_max`` on the program's ``serve.engine.step`` rows. The mean
load is 64 slots x 2 rows x 8 picks / 256 experts = 4 rows an expert; this is
the straggler."""

from perfbench import gigachat_bytes as gb


def read(ctx):
    return gb.per_step(ctx, "expert_tokens_max")
