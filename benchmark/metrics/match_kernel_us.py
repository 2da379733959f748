"""Device time of one call of the match kernel (ops/hash_index.py
`match_ids_hash`), averaged over its calls in the window (device trace)."""

KERNEL = "match_ids_hash"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = ctx.trace.kernel_calls(ctx.kernel(KERNEL).TRACE_NAMES)
    if not calls:
        return None
    return 1e6 * sum(d for d, _ in calls) / len(calls)
