"""Deliveries, of any publish, that arrived inside the window, per second
of the window (host clock)."""


def read(ctx):
    n = ctx.deliveries_in_window()
    return n / ctx.seconds if n else None
