"""Pages the sparse layers' attention read over the pages the same slots
hold, over the window's decode steps (a share of counts): ``sparse_pages_read``
and ``sparse_pages_live`` on the program's ``serve.engine.step`` rows, both
per K/V head and layer. 100 would be the whole table, as the dense path
reads it."""

from perfbench import sala_bytes


def read(ctx):
    steps = sala_bytes.sparse_steps(ctx)
    live = sum(f["sparse_pages_live"] for f in steps)
    if not live:
        return None
    return 100.0 * sum(f["sparse_pages_read"] for f in steps) / live
