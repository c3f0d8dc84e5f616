"""The least bytes a decode step of the gated-short-convolution / attention
family must move (``perfbench.lfm2_bytes.decode_min_bytes``: every weight
outside the experts with the tied table once as the head, the experts the
step hit from its own ``experts_hit``, the keys and values the active slots'
queries attend from its ``context_positions``, the conv tails read and
written, one K/V row written a slot an attention layer) over the chip's HBM
bandwidth, over the decode program's device time: the share of the whole
step. Bytes-bound. The counters come from the program's ``serve.engine.step``
rows (a program without them gives nothing to read)."""

from perfbench import lfm2_bytes as lb, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    hit = lb.per_step(ctx, "experts_hit")
    if device_s is None or hit is None or not ctx.get("peaks"):
        return None
    need = lb.decode_min_bytes(
        ctx["shape"], hit, lb.per_step(ctx, "context_positions"),
        lb.per_step(ctx, "moe_rows"))
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
