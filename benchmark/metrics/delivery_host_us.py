"""Host time delivery spends per publish: the window's
`emqx.plan_resolve` + `emqx.dispatch_loop` + `emqx.session_write` +
`emqx.ack_sweep` stage spans (plan build, fan walk, session writes, QoS
bookkeeping), over the engine's publishes in the window, in
microseconds (program spans)."""

import hostspans

STAGES = ("plan_resolve", "dispatch_loop", "session_write", "ack_sweep")


def read(ctx):
    return hostspans.per_unit_us(ctx, STAGES, ctx.publishes)
