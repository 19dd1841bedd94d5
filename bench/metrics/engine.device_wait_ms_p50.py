"""Median time the host waits for the device to finish a batch, in ms: the
``engine.device_wait`` span (``block_until_ready`` on the output), over the
window's dispatches."""
from bench import phases


def read(records):
    return phases.median_ms(records, "engine.device_wait")
