"""Median of ``serve.pump.deliver``: ``step()``'s return in the executor
thread -> its last token queued on the replica's event loop. The part of
``pump_ms_per_token`` that is the replica's own."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.DELIVER)
