"""Held experts that got at least one token, per layer (every layer has an
expert layer behind its mixer), averaged over the window's decode steps (a
count): ``experts_hit`` on the program's ``serve.engine.step`` rows is the
sum over the layers. Of the 36 held: at 64 slots x 10 picks over 72 experts a
router that spreads its picks leaves hardly one without a token, and seeded
routers that do not would show here."""

from perfbench import granite_bytes as gb


def read(ctx):
    hits = gb.per_step(ctx, "experts_hit")
    layers = len(gb.kinds(ctx["shape"]))
    return None if hits is None or not layers else hits / layers
