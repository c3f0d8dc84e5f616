"""The least bytes a decode step of the latent-attention / zero-expert family
must move (``perfbench.longcat_bytes.decode_min_bytes``: the weights without
the embedding table, counted from shapes; the cached latent rows of the
active slots read once a sublayer, from the step's own ``latent_positions``;
one row written a slot a sublayer) over the chip's HBM bandwidth, over the
decode program's device time. Bytes-bound. The counters come from the
program's ``serve.engine.step`` rows (a program without them gives nothing
to read)."""

from perfbench import longcat_bytes as lb, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    positions = lb.per_step(ctx, "latent_positions")
    if device_s is None or positions is None or not ctx.get("peaks"):
        return None
    need = lb.decode_min_bytes(ctx["shape"], positions,
                               lb.per_step(ctx, "moe_rows"))
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
