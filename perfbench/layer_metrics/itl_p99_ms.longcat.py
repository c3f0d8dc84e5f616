"""99th percentile of the gap between consecutive streamed tokens, pooled
over all gaps that end in the window, at the client: what ``itl_p99_ms`` is
in the cells that hold it end to end. Here ten to fifteen admissions of
about a second each lie in a window of some 28 000 gaps, a few hundred of
which span an admission: the 99th percentile sits beside that cliff and
moves with the count of admissions a window holds, so it stands per layer,
as ``itl_p99_ms.sala`` does (PERF.md section 6, PR 34 has the spread).

Per-layer metrics are read in the traced run, and the profiler slows the
replica from the middle of the window on: hold a traced reading against
traced readings only."""

from perfbench import stats


def read(ctx):
    gaps = ctx["summary"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
