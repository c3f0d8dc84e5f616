"""Seconds the factory / train loop spent making the weights on the device."""


def read(ctx):
    return ctx["setup"].get("weights_s")
