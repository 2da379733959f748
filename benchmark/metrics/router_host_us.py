"""Host time the router spends per device batch encoding topics and
unpacking results (`encode` + `unpack` leg sums / `dispatch_batches_total`
deltas; program counters and host times)."""


def read(ctx):
    n = ctx.counters.get("dispatch_batches_total", 0)
    if not n:
        return None
    s = sum(ctx.legs.get(leg, (0.0, 0))[0] for leg in ("encode", "unpack"))
    return 1e6 * s / n
