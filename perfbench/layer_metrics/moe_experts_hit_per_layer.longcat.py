"""Held experts that got at least one token, per expert layer, averaged over
the window's decode steps (a count): ``experts_hit`` on the program's
``serve.engine.step`` rows is the sum over the expert layers, one a double
layer. What ``moe_experts_hit_per_layer`` is in the hybrid cell, whose reader
counts the layers in a pattern string this family has none of."""

from perfbench import longcat_bytes as lb


def read(ctx):
    hits = lb.per_step(ctx, "experts_hit")
    return None if hits is None else hits / ctx["shape"]["num_layers"]
