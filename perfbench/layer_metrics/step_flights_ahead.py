"""Median ``depth`` of the window's ``serve.step.flight`` rows that followed
no admission: the steps already in flight when one more was dispatched (the
cap is 10 with every slot held, 2 while one is free)."""

from perfbench import flight_spans


def read(ctx):
    return flight_spans.step_flights_ahead(ctx)
