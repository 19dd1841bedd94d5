"""Median time to place a batch's operands on the device, in ms: the
``engine.upload`` span (``jax.device_put``), over the window's dispatches."""
from bench import phases


def read(records):
    return phases.median_ms(records, "engine.upload")
