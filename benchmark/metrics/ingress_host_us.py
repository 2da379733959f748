"""Host time the listener and channel spend per publish: the window's
`emqx.decode` + `emqx.channel` + `emqx.ack_write` stage spans (frame
decode, packet handling, PUBACK writes), over the engine's publishes in
the window, in microseconds (program spans)."""

import hostspans

STAGES = ("decode", "channel", "ack_write")


def read(ctx):
    return hostspans.per_unit_us(ctx, STAGES, ctx.publishes)
