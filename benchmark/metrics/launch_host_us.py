"""Host time the router spends per device batch in the jit dispatch and
the start of the result transfer: the window's `emqx.launch` +
`emqx.ticket_start` stage spans over `dispatch_batches_total`, in
microseconds (program spans). `router_host_us` holds the encode and
unpack legs, not these."""

import hostspans

STAGES = ("launch", "ticket_start")


def read(ctx):
    return hostspans.per_unit_us(
        ctx, STAGES, ctx.counters.get("dispatch_batches_total", 0)
    )
