"""Pairs (row, expert) of the window's decode steps that went to a
zero-compute expert, over all the pairs those steps routed (a share of
counts): ``zero_picks`` over ``moe_topk x moe_rows x expert layers`` of the
program's ``serve.engine.step`` rows. A calibrated router picks all 768
outputs alike, so a third."""

from perfbench import longcat_bytes as lb


def read(ctx):
    rows = lb.latent_steps(ctx)
    pairs = sum(f["moe_rows"] for f in rows) * ctx["shape"]["moe_topk"] \
        * ctx["shape"]["num_layers"]
    if not pairs:
        return None
    return 100.0 * sum(f["zero_picks"] for f in rows) / pairs
