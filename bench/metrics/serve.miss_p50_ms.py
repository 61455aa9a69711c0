"""Median latency of the queries the server answered as lane-cache
misses, from their scheduled send time, from the benchmark's own
records."""

import numpy as np


def read(run):
    lat = run.records.get("latency_ms")
    miss = run.records.get("miss")
    if lat is None or miss is None or not miss.any():
        return None
    return float(np.median(lat[miss]))
