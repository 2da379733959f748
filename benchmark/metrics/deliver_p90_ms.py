"""90th percentile of due time to delivery, over every delivery of the
window's publishes (host clock, CLOCK_MONOTONIC)."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_ns()
    if len(lat) < 100:  # ten samples beyond the 90th percentile
        return None
    return float(np.percentile(lat, 90)) / 1e6
