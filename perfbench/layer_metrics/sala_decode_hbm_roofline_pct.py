"""The least bytes a decode step of the lightning / block-sparse family must
move (``perfbench.sala_bytes.decode_min_bytes``: the weights without the
embedding table, the active slots' lightning state read and written, the
pages and compressed keys the step's counters say its sparse layers read, one
K/V row written) over the chip's HBM bandwidth, over the decode program's
device time. Bytes-bound. The counters come from the program's
``serve.engine.step`` rows (a program without them gives nothing to read)."""

from perfbench import sala_bytes, serve_spans
from perfbench.flops import roofline_pct


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    steps = sala_bytes.sparse_steps(ctx)
    if device_s is None or not steps or not ctx.get("peaks"):
        return None
    n = len(steps)
    need = sala_bytes.decode_min_bytes(
        ctx["shape"], sum(f["active"] for f in steps) / n,
        sum(f["sparse_pages_read"] for f in steps) / n,
        sum(f["sparse_pages_live"] for f in steps) / n)
    return roofline_pct(need, device_s, ctx["peaks"]["hbm_bytes_per_s"])
