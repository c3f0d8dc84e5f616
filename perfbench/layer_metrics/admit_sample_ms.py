"""Median of ``serve.admit.sample``: the key split and the blocking fetch of
the first token, where the host waits for what it dispatched before."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.SAMPLE)
