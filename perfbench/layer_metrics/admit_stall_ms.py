"""95th percentile of the wall time of one ``_admit`` call that admitted
something: how long every decoding slot stood still."""

from perfbench import serve_spans, stats


def read(ctx):
    calls = serve_spans.admits(ctx)
    if not calls:
        return None
    return stats.percentile([(a[1] - a[0]) / 1e6 for a in calls], 95)
