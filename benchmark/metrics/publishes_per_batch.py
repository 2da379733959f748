"""Publishes the dispatch engine closed into each batch over the window
(engine `publishes_total` / `batches_total` deltas; program counters)."""


def read(ctx):
    return ctx.publishes / ctx.batches if ctx.batches else None
