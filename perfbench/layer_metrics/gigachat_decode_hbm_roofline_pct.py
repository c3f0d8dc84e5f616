"""The least bytes a step of the DeepSeek-V3 family must move
(``perfbench.gigachat_bytes.decode_min_bytes``: the weights every step reads,
counted from shapes; the routed experts the step HIT, the LIVE latent rows of
the active slots read once a layer and the rows it writes, from the step's
own ``experts_hit``, ``latent_positions`` and ``moe_rows``) over the chip's
HBM bandwidth, over the step program's device time. Bytes-bound. The counters
come from the program's ``serve.engine.step`` rows (a program without them
gives nothing to read)."""

from perfbench import gigachat_bytes as gb, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    positions = gb.per_step(ctx, "latent_positions")
    if device_s is None or positions is None or not ctx.get("peaks"):
        return None
    need = gb.decode_min_bytes(ctx["shape"], positions,
                               gb.per_step(ctx, "moe_rows"),
                               gb.per_step(ctx, "experts_hit"))
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
