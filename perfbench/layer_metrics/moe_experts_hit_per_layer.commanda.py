"""Held experts that got at least one token, per expert layer, averaged over
the window's decode steps (a count): ``moe_hit`` on the program's
``serve.engine.step`` rows is the sum over the layers, each of which has an
expert layer. What ``moe_experts_hit_per_layer`` is in the hybrid cell, whose
reader counts the layers in a pattern string this family has none of."""

from perfbench import commanda_bytes as cb


def read(ctx):
    hits = cb.per_step(ctx, "moe_hit")
    return None if hits is None else hits / ctx["shape"]["num_hidden_layers"]
