"""``step_flights_ahead`` where the slots' positions ride from flight to
flight on the device: median ``depth`` of the window's ``serve.step.flight``
rows that followed no admission (the cap is 10 with every slot held; a
stream's budget is counted at two tokens a flight, so the depth falls
earlier before a stream's end than where a step yields one)."""

from perfbench import flight_spans


def read(ctx):
    return flight_spans.step_flights_ahead(ctx)
