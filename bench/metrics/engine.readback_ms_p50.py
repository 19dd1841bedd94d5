"""Median time to copy a finished batch's result to the host, in ms: the
``engine.readback`` span (``np.asarray`` of the ready output), over the
window's dispatches."""
from bench import phases


def read(records):
    return phases.median_ms(records, "engine.readback")
