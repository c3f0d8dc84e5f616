"""99th percentile of the gap between consecutive streamed tokens, pooled
over all gaps that end in the window, at the client: what ``itl_p99_ms`` is
in the cells that hold it end to end. Here a few dozen admissions of a tenth
of a second to more than a second lie in a window of some 90 000 gaps, about
a thousand of which span an admission: the 99th percentile sits on that
cliff and moves with the admissions a window holds, so it stands per layer,
as ``itl_p99_ms.sala`` and ``itl_p99_ms.longcat`` do.

Per-layer metrics are read in the traced run, and the profiler slows the
replica from the middle of the window on: hold a traced reading against
traced readings only."""

from perfbench import stats


def read(ctx):
    gaps = ctx["summary"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
