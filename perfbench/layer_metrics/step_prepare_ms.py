"""Median of ``serve.step.prepare``: reaping, page-table growth and the
uploads of a step's host arrays, up to the decode program's call."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.PREPARE)
