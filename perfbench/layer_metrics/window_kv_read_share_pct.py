"""Positions a window layer's query attends over the positions a full layer's
does, over the window's decode steps (a share of counts):
``window_positions`` (each active slot's context capped at the window) over
``context_positions`` on the program's ``serve.engine.step`` rows. 100 is a
window layer that reads as a full one: every context under the window, or a
window layer kept as pages of the whole context."""

from perfbench import commanda_bytes as cb


def read(ctx):
    rows = cb.steps(ctx)
    context = sum(f["context_positions"] for f in rows)
    if not context:
        return None
    return 100.0 * sum(f["window_positions"] for f in rows) / context
