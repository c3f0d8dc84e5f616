"""The least bytes a decode step of the Granite-MoE-hybrid family must move
(``perfbench.granite_bytes.decode_min_bytes``: every weight outside the
routed experts with the tied table once as the head, the experts the step HIT
from its own ``experts_hit`` and not the 36 held, the active slots' float32
SSM state and tails read and written, the keys and values their queries
attend from its ``context_positions``, the embedding rows) over the chip's HBM
bandwidth, over the decode program's device time: the share of the whole
step. Bytes-bound. The counters come from the program's ``serve.engine.step``
rows (a program without them gives nothing to read)."""

from perfbench import granite_bytes as gb, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    need = gb.step_min_bytes(ctx)
    if device_s is None or need is None or not ctx.get("peaks"):
        return None
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
