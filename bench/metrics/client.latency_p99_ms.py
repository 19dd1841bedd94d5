"""The 99th percentile of the latency that clients saw, in ms, over
every request of the window that completed: the tail that whole-process
stalls of the host set, so it is read per run and bounded nowhere."""
import numpy as np


def read(records):
    lat = records["latencies_ms"]
    return float(np.percentile(lat, 99)) if len(lat) else None
