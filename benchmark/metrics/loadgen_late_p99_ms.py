"""99th percentile of how late the load generator sent a publish after
its due time, in an open loop (host clock)."""

import numpy as np


def read(ctx):
    if ctx.loop() != "open":
        return None
    late = ctx.late_ns()
    if len(late) < 1000:
        return None
    return float(np.percentile(late, 99)) / 1e6
