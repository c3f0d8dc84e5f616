"""Device time of the prefill program's executions in the trace over the
prompt tokens of the admissions that began inside the traced window (the
prefill is an admission's first act). About one seed in sixteen puts no
admission's start into the traced 6 s: then there is nothing to read."""

from perfbench import program_spans as ps, xplane


def read(ctx):
    red = ctx.get("trace")
    begin = ((ctx.get("host") or {}).get("window_ns") or (0, 0))[0]
    tokens = sum(f["prompt_len"] for start, _, f in ps.in_trace(ctx, ps.ADMIT)
                 if start >= begin)
    if not red or not tokens:
        return None
    times = xplane.module_times(red, ps.PREFILL_MODULE)
    return sum(times) * 1e3 / tokens if times else None
