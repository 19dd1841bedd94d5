"""Median time to enqueue the batch executable, in ms: the ``engine.launch``
span, from the compiled executable's call to its return, over the window's
dispatches."""
from bench import phases


def read(records):
    return phases.median_ms(records, "engine.launch")
