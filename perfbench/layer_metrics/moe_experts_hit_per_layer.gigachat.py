"""Held experts that got at least one row, per expert layer, averaged over
the window's steps (a count): ``experts_hit`` on the program's
``serve.engine.step`` rows is the sum over the expert layers, the decoder's
and the MTP block's. A row reaches this chip only when routing group 0 is
among its four kept groups."""

from perfbench import gigachat_bytes as gb


def read(ctx):
    hits = gb.per_step(ctx, "experts_hit")
    return None if hits is None else hits / gb.expert_layers(ctx["shape"])
