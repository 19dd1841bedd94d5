"""Median queue wait in the engine, in ms: from a request's submit to
its first ``dispatch`` event, over the engine's request spans of the
window."""
import numpy as np


def read(records):
    waits = []
    for span in records["spans"]:
        first = next((e["t"] for e in span["events"] if e["name"] == "dispatch"), None)
        if first is not None:
            waits.append((first - span["t_start"]) * 1e3)
    return float(np.percentile(waits, 50)) if waits else None
