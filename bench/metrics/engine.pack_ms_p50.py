"""Median time the engine takes to pack a batch on the host, in ms: the
``engine.pack`` span, from the padded operands' ``np.zeros`` to the last
request's row copied in, over the window's dispatches."""
from bench import phases


def read(records):
    return phases.median_ms(records, "engine.pack")
