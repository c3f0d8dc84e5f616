"""1 - device busy over the traced steps (first traced step's start to the
last one's end), averaged over the devices."""


def read(ctx):
    t = ctx.get("trace") or {}
    busy, span = t.get("busy_in_span_s"), t.get("steps_span_s")
    if not busy or not span:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
