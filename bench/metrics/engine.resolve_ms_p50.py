"""Median time to hand a batch's results to its requests, in ms: the
``engine.resolve`` span (futures, request spans, counters), over the window's
dispatches."""
from bench import phases


def read(records):
    return phases.median_ms(records, "engine.resolve")
