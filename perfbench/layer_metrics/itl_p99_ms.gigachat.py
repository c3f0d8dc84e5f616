"""99th percentile of the gap between consecutive streamed tokens, pooled
over all gaps that end in the window, at the client: what ``itl_p99_ms`` is
in the cells that hold it end to end. Here a step hands a slot one or two
tokens at once (the second's gap is the pump's hand-off, near 0) and some
thirty admissions of 0.15-0.6 s lie in a window, so the 99th percentile sits
beside that cliff and moves with the count of admissions a window holds: it
stands per layer, as ``itl_p99_ms.longcat`` does.

Per-layer metrics are read in the traced run, and the profiler slows the
replica from the middle of the window on: hold a traced reading against
traced readings only."""

from perfbench import stats


def read(ctx):
    gaps = ctx["summary"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
