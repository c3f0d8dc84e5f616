"""The share of the window the decoding streams lost to admissions: over the
window's consecutive ``serve.step.flight`` rows, what the landing interval of
a step that followed an admission had over the median interval of the steps
that followed none, summed, over the window less the profiler's two calls
(what an interval has inside one of them is taken off it)."""

from perfbench import flight_spans


def read(ctx):
    return flight_spans.admit_stall_share_pct(ctx)
