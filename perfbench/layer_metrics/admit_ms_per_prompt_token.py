"""Wall time inside ``_admit`` over the prompt tokens it admitted."""

from perfbench import serve_spans


def read(ctx):
    calls = serve_spans.admits(ctx)
    tokens = sum(a[3] for a in calls)
    if not tokens:
        return None
    return sum(a[1] - a[0] for a in calls) / 1e6 / tokens
