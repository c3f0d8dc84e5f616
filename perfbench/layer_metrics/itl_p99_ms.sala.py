"""99th percentile of the gap between consecutive streamed tokens, pooled
over all gaps that end in the window, at the client: what ``itl_p99_ms`` is
in the cells that hold it end to end.

In the long-context cell the engine runs ahead of the device, so a gap is the
device's step (15.8 ms, the same to a hundredth from run to run) and this
percentile sits 2-4 ms above it, in the jitter of the path from ``step()``'s
return to the client, with a window's 7 admissions and the machine's own
pauses (0 to 4 of ~0.1 s) taking a changing share of the top hundredth: six
seeds spread 2.3 % and, before a step's tokens were fetched as it ended,
3.8 % (my chip runs, PR 32), against the 3.5 % an end-to-end metric with the
bound 0.07 may spread. So it stands here, as issue 32 expected.

Per-layer metrics are read in the traced run, and the profiler slows the
replica from the middle of the window on: one seed read 20.9 ms traced and
17.6 untraced (my chip runs, PR 32). Hold a traced reading against traced
readings only."""

from perfbench import stats


def read(ctx):
    gaps = ctx["summary"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
