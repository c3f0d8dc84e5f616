"""Median ``wait_ns`` of the window's ``serve.step.flight`` rows: how long the
host stood blocked in ``device_get`` for a step's tokens, the room its call
has under the device's step. It FALLS when a PR shortens the step and the
host's call stays; at ~0 the host paces the engine."""

from perfbench import flight_spans


def read(ctx):
    return flight_spans.step_host_slack_ms(ctx)
