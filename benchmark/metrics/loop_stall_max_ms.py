"""The broker event loop's worst lateness in the window: a 10 ms ticker
on the loop keeps how late it woke (host clock)."""


def read(ctx):
    return 1e3 * ctx.loop_stall_s
