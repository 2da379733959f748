"""Verified (topic, filter) pairs the device match gave each topic it
answered, over the window (`match_device_pairs_total` /
`match_device_topics_total` deltas; program counters). Cache hits and
batches re-matched on the host trie are in neither count."""


def read(ctx):
    n = ctx.counters.get("match_device_topics_total", 0)
    return ctx.counters.get("match_device_pairs_total", 0) / n if n else None
