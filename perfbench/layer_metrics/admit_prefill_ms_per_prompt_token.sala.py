"""Host time in ``serve.admit.prefill`` of the window's admissions (a host
loop over the chunk program's dispatches, until the last returns) over the
prompt tokens they admitted: what ``admit_prefill_ms_per_prompt_token`` is in
the chat cell, in a cell whose end-to-end metric is the output rate (seven
streams wait while a successor's 13k-41k tokens are admitted)."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.ms_per_prompt_token(ctx, ps.PREFILL)
