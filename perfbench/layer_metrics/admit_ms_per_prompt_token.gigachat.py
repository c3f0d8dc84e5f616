"""The whole ``serve.engine.admit`` span of the window's admissions (the
chunk program's dispatches with the MTP block one token on, the scatter, the
first token's sample and the first draft's dispatch) over the prompt tokens
they admitted, as ``admit_ms_per_prompt_token.longcat`` is. Sixty-three
streams wait while a successor's 1k-8k tokens are admitted."""

from perfbench import program_spans as ps


def read(ctx):
    admits = ps.in_window(ctx, ps.ADMIT)
    tokens = sum(f["prompt_len"] for f in admits)
    if not tokens:
        return None
    return sum(f["dur_ns"] for f in admits) / 1e6 / tokens
