"""``step_host_slack_ms`` where a flight lands one or two tokens a slot:
median ``wait_ns`` of the window's ``serve.step.flight`` rows, how long the
host stood blocked in ``device_get`` for a step's tokens. The host's call
emits up to twice the events a step here (``tokens`` on the row) under the
same device step, so this is the room that is left; at ~0 the host paces the
engine. (The accepted reader's list is held to two cells by its test.)"""

from perfbench import flight_spans


def read(ctx):
    return flight_spans.step_host_slack_ms(ctx)
