"""The least bytes a decode step of the window / full attention family must
move (``perfbench.commanda_bytes.decode_min_bytes``: every weight, counted
from shapes; the keys and values the active slots' queries attend, a full
layer's from the step's own ``context_positions`` and a window layer's from
its ``window_positions``; one row written a slot a layer) over the chip's HBM
bandwidth, over the decode program's device time: the share of the whole
step. Bytes-bound. The counters come from the program's ``serve.engine.step``
rows (a program without them gives nothing to read)."""

from perfbench import commanda_bytes as cb, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    context = cb.per_step(ctx, "context_positions")
    if device_s is None or context is None or not ctx.get("peaks"):
        return None
    need = cb.decode_min_bytes(
        ctx["shape"], context, cb.per_step(ctx, "window_positions"),
        cb.per_step(ctx, "moe_rows"))
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
