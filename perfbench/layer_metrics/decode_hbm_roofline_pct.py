"""The least bytes a decode step must read (weights once + the live K/V of
the active sequences, ``perfbench.flops.decode_min_bytes``) over the chip's
HBM bandwidth, over the decode program's device time. Bytes-bound."""

from perfbench import flops, serve_spans


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    steps = serve_spans.steps_that_decoded(ctx)
    if device_s is None or not steps or not ctx.get("peaks"):
        return None
    live = sum(s[5] for s in steps) / len(steps)
    slots = sum(s[3] for s in steps) / len(steps)
    need = flops.decode_min_bytes(ctx["shape"], live, slots)
    return flops.roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
