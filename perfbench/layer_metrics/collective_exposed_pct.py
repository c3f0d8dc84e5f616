"""Share of the step's device time in which a collective operation is what
the core executes (so no compute does), on the device where it is largest."""


def read(ctx):
    t = ctx.get("trace") or {}
    shares = [100.0 * c / (s * n)
              for c, s, n in zip(t.get("collective_s") or [],
                                 t.get("step_device_s") or [],
                                 t.get("n_executions") or []) if s and n]
    return max(shares) if shares else None
