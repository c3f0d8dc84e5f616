"""The recurrent state a decode step's Mamba-2 recurrences read and write
(``ssm_state_bytes`` on the program's ``serve.engine.step`` rows: every
active slot's float32 SSM state and its tails, once in and once out, from
shapes and the active rows) over the least bytes the whole step must move
(``perfbench.granite_bytes.decode_min_bytes`` from the same rows' counters):
a share of counts. At 64 slots of 38.2 MB it is a third of the step; a
change of the state's dtype or layout, or of the batch, moves it."""

from perfbench import granite_bytes as gb


def read(ctx):
    need = gb.step_min_bytes(ctx)
    if not need:
        return None
    return 100.0 * gb.per_step(ctx, "ssm_state_bytes") / need
