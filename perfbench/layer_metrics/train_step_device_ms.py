"""Device time per execution of the train step program, slowest device."""


def read(ctx):
    t = ctx.get("trace") or {}
    times = [s for s in t.get("step_device_s") or [] if s]
    return max(times) * 1e3 if times else None
