"""99th percentile of the gap between consecutive streamed tokens, pooled
over all gaps that end in the window, at the client: what ``itl_p99_ms`` is
in the cells that hold it end to end. Here some eighty admissions of one to
four chunks (0.1-0.6 s each) lie in a window, 63 gaps spanning each: more
than one gap in a hundred, so the 99th percentile sits on the admissions' own
spread and moves with the prompts a window admits; it stands per layer, as
``itl_p99_ms.sala`` and ``.lfm2`` do.

Per-layer metrics are read in the traced run, and the profiler slows the
replica from the middle of the window on: hold a traced reading against
traced readings only."""

from perfbench import stats


def read(ctx):
    gaps = ctx["summary"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
