"""1 - device busy over the time the engine had work (the union of the
``engine.step()`` spans inside the traced window)."""

from perfbench import serve_spans


def read(ctx):
    return serve_spans.device_idle_pct(ctx)
