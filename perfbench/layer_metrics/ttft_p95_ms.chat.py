"""95th percentile of due instant -> first streamed token over every
measured request of the window, at the client: what ``ttft_p95_ms`` was end
to end until PR 27. The chat window holds 18 requests, so this is nearly
their maximum, and which arrival meets a 97 ms step in flight decides it:
two sets of six runs of one code spread 9.9 % and 7.9 % of their median in
the driver's check of PR 27, where 5 % is the most any bound admits. So it
stands here, with no bound, beside ``ttft_p50_ms.chat``, and the cell is
judged end to end on ``latency_ms_per_out_token``, which holds every first
token's wait at its weight in the whole request.

Read in the traced run; since PR 26 the traced tail reads as the untraced
one (222-299 ms against 232-311, my chip runs, PR 27)."""

from perfbench import stats


def read(ctx):
    ttft = ctx["summary"]["ttft_ms"]
    return stats.percentile(ttft, 95) if ttft else None
