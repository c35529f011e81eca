"""Median latency, ms: from each request's due time to its outputs on
the host, over every request due in the window (open loop only)."""

import numpy as np


def read(record):
    lat = record.get("latencies_ms")
    return float(np.percentile(lat, 50)) if lat else None
