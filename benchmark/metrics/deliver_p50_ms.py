"""Median time from a publish's due time to a delivery, over every
delivery of the window's publishes (host clock, CLOCK_MONOTONIC)."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_ns()
    if not len(lat):
        return None
    return float(np.percentile(lat, 50)) / 1e6
