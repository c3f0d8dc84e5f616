"""The whole ``serve.engine.admit`` span of the window's admissions (the
chunk program's dispatches, the scatter and the first token's sample, until
that token is on the host) over the prompt tokens they admitted. Not
``serve.admit.prefill`` alone: that span closes at the last chunk's dispatch,
and on the sparse cell read 0.00098 ms a token beside the 0.0735 its fill
costs (ledger, PR 33). Fifteen streams wait while a successor's 4k-12k
tokens are admitted."""

from perfbench import program_spans as ps


def read(ctx):
    admits = ps.in_window(ctx, ps.ADMIT)
    tokens = sum(f["prompt_len"] for f in admits)
    if not tokens:
        return None
    return sum(f["dur_ns"] for f in admits) / 1e6 / tokens
