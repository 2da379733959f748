"""Mean wait of a publish in the dispatch engine's queue before its batch
closed (`pipeline_queue_wait_seconds` sum / count deltas; program
counters)."""


def read(ctx):
    s, n = ctx.queue_wait
    return 1e3 * s / n if n else None
