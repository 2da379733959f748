"""Share of the traced window in which no operation ran on the chip:
100 x (1 - union of device operation intervals / window), averaged over
the chips the trace shows (device trace)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.busy_s or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_mean_s / tr.window_s)
