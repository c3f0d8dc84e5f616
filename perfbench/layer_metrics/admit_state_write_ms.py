"""Median of ``serve.admit.state``: the host's side of writing one
admission's recurrent state (SSM state and convolution tail of every Mamba
layer) into its slot, one dispatch."""

from perfbench import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, "serve.admit.state")
