"""Median of ``submit()`` -> the start of the request's ``serve.engine.admit``
span (its ``waited_ns``), over the admissions of the window: queue wait as the
engine sees it, without router, handle and transport ingress."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.ADMIT, "waited_ns")
