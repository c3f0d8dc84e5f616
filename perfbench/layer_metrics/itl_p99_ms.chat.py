"""99th percentile of the gap between consecutive streamed tokens, pooled
over all gaps that end in the window, at the client: what ``itl_p99_ms`` is
in the cells that hold it end to end. Here its runs spread too widely for
any bound the contract allows, so it stands beside ``admit_stall_ms``.

Per-layer metrics are read in the traced run, and the profiler slows the
replica from the middle of the window on (its stop alone takes 12-17 s beside
the engine): one seed read 1 502-1 851 ms traced and 1 082-1 206 ms untraced
(my chip runs, PR 23). Hold a traced reading against traced readings only."""

from perfbench import stats


def read(ctx):
    gaps = ctx["summary"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
