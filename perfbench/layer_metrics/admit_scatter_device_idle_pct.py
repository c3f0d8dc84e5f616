"""1 - device busy inside the parts of the ``serve.admit.scatter`` spans that
lie in the traced window, on the trace's clock: high where the scatter is
bound by the host's dispatch, low where the device is what it waits for."""

from perfbench import program_spans as ps, stats


def read(ctx):
    red = ctx.get("trace") or {}
    window = (ctx.get("host") or {}).get("window_ns")
    if not window or not red.get("busy_intervals"):
        return None
    scatter = stats.intersect(
        stats.merge((a, b) for a, b, _ in ps.in_trace(ctx, ps.SCATTER)),
        [tuple(window)])
    total = sum(b - a for a, b in scatter)
    if total <= 0:
        return None
    busy = sum(b - a for a, b in
               stats.intersect(red["busy_intervals"], scatter))
    return 100.0 * (1.0 - busy / total)
