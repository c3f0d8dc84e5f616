"""Device time per execution of the decode program, from the trace."""

from perfbench import serve_spans


def read(ctx):
    s = serve_spans.decode_device_s(ctx)
    return None if s is None else s * 1e3
