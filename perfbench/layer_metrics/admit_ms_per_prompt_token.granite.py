"""The whole ``serve.engine.admit`` span of the window's admissions (the
chunk program's dispatches with the recurrent state carried from chunk to
chunk, the scatter, the state's write and the first token's sample, until
that token is on the host) over the prompt tokens they admitted, as
``admit_ms_per_prompt_token.longcat`` and ``.lfm2`` are and for their reason
(``serve.admit.prefill`` alone closes at the last chunk's dispatch). Some
eighty admissions of one to four chunks lie in a window while 63 streams
wait."""

from perfbench import program_spans as ps


def read(ctx):
    admits = ps.in_window(ctx, ps.ADMIT)
    tokens = sum(f["prompt_len"] for f in admits)
    if not tokens:
        return None
    return sum(f["dur_ns"] for f in admits) / 1e6 / tokens
