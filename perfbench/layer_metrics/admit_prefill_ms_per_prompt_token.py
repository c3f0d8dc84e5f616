"""Host time in ``serve.admit.prefill`` (padding and the prefill program's
dispatch, until the call returns) over the prompt tokens admitted."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.ms_per_prompt_token(ctx, ps.PREFILL)
