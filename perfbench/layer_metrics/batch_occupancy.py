"""Mean active slots per decode step of the window (a count)."""

from perfbench import serve_spans


def read(ctx):
    steps = serve_spans.steps_that_decoded(ctx)
    if not steps:
        return None
    return sum(s[3] for s in steps) / len(steps)
