"""Median of due instant -> first streamed token over every measured
request of the window, at the client: the wait for a step in flight, the
admission, the admitting step and the pump (about 45 + 22 + 97 + 3 ms since
PR 26). Steadier than ``ttft_p95_ms.chat`` (sets of six spread 6-9 %), still
too wide for a bound."""

from perfbench import stats


def read(ctx):
    ttft = ctx["summary"]["ttft_ms"]
    return stats.median(ttft) if ttft else None
