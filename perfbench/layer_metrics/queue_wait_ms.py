"""Median of due instant -> start of the ``_admit`` call that took the
request, over the window's measured requests."""

from perfbench import stats


def read(ctx):
    starts = ctx["spans"].get("admit_start_ns") or {}
    waits = [starts[s.req.index] / 1e6 - s.due * 1e3
             for s in ctx["run"]["sent"]
             if s.req.measured and s.req.index in starts]
    return stats.median(waits) if waits else None
