"""Median of client receive time minus the end of the ``engine.step()`` that
emitted the token: the replica's pump and the handle's stream."""

from perfbench import stats


def read(ctx):
    ends = ctx["spans"].get("token_step_end_ns") or {}
    run = ctx["run"]
    lags = []
    for s in run["sent"]:
        for recv, end in zip(s.times, ends.get(s.req.index, [])):
            if run["t_open"] <= recv < run["t_close"]:
                lags.append(recv * 1e3 - end / 1e6)
    return stats.median(lags) if lags else None
