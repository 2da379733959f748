"""Share of the window's batched topics the router's match cache answered
(`match_cache_hits` / (hits + misses) deltas; program counters)."""


def read(ctx):
    h = ctx.counters.get("match_cache_hits", 0)
    m = ctx.counters.get("match_cache_misses", 0)
    return 100.0 * h / (h + m) if h + m else None
