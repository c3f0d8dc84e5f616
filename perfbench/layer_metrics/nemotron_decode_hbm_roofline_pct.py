"""The least bytes a decode step of the hybrid family must move
(``perfbench.nemotron_bytes.decode_min_bytes``: the weights outside the
routed experts, the held experts that got a token, the active slots' state
read and written, live K/V, the head) over the chip's HBM bandwidth, over the
decode program's device time. Bytes-bound. ``experts_hit`` comes from the
program's ``serve.engine.step`` rows (a program without them gives nothing
to read)."""

from perfbench import nemotron_bytes, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    steps = serve_spans.steps_that_decoded(ctx)
    hits = nemotron_bytes.experts_hit_per_step(ctx)
    if device_s is None or not steps or not hits or not ctx.get("peaks"):
        return None
    live = sum(s[5] for s in steps) / len(steps)
    slots = sum(s[3] for s in steps) / len(steps)
    need = nemotron_bytes.decode_min_bytes(ctx["shape"], live, slots,
                                           sum(hits) / len(hits))
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
