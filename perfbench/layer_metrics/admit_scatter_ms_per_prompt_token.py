"""Host time in ``serve.admit.scatter`` (the per-layer, per-page K/V scatter
loop) over the prompt tokens admitted."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.ms_per_prompt_token(ctx, ps.SCATTER)
