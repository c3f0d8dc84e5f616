"""Median of ``serve.step.fetch``: the host blocked on the step's tokens and
keys (the decode program's device time less what dispatch overlapped)."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.FETCH)
