"""Held experts that got at least one token, per expert layer, averaged over
the window's decode steps (a count): ``experts_hit`` on the program's
``serve.engine.step`` rows is the sum over the expert layers."""

from perfbench import nemotron_bytes


def read(ctx):
    layers = (ctx["shape"].get("hybrid_override_pattern") or "").count("E")
    hits = nemotron_bytes.experts_hit_per_step(ctx)
    if not hits or not layers:
        return None
    return sum(hits) / len(hits) / layers
