"""Experts that got at least one token, per expert layer, averaged over the
window's decode steps (a count): ``experts_hit`` on the program's
``serve.engine.step`` rows is the sum over the expert layers (every layer
after the leading dense ones). All 64 experts of a layer are held here, so
this is of 64: at 64 slots x 4 picks a router that spreads its picks leaves
one expert in sixty without a token. What ``moe_experts_hit_per_layer`` is in
the hybrid cell, whose reader counts the layers in a pattern string this
family has none of."""

from perfbench import lfm2_bytes as lb


def read(ctx):
    hits = lb.per_step(ctx, "experts_hit")
    layers = lb.n_expert_layers(ctx["shape"])
    return None if hits is None or not layers else hits / layers
