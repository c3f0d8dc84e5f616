"""The device's idle time in the ``serve.step.prepare`` / ``.dispatch`` spans
of the calls that dispatched a step with nothing in flight (``depth`` 0 on its
``serve.step.flight`` row), over the traced window: the restart after an
admission drained the queue."""

from perfbench import flight_spans


def read(ctx):
    return flight_spans.device_idle_restart_pct(ctx)
