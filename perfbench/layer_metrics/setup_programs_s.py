"""Seconds to lower, compile or load from the cache, and warm up the cell's
shapes (engine construction included; weights excluded)."""


def read(ctx):
    return ctx["setup"].get("programs_s")
