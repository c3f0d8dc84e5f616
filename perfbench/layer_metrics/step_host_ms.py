"""Median wall time of ``step()`` calls that admitted nothing, minus the
device time of their decode program: the per-token host path."""

from perfbench import serve_spans, stats


def read(ctx):
    steps = serve_spans.decode_steps(ctx)
    device_s = serve_spans.decode_device_s(ctx)
    if not steps or device_s is None:
        return None
    return stats.median([(s[1] - s[0]) / 1e6 for s in steps]) - device_s * 1e3
