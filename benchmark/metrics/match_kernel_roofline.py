"""The match kernel's share of its roofline: the least time the chip
could take for its calls (benchmark/kernels/match_ids_hash.py: the larger
of operations over the peak rate and bytes over HBM bandwidth, from the
call's shapes and benchmark/peaks.json), over the kernel's device time
in the trace, in percent."""

KERNEL = "match_ids_hash"


def read(ctx):
    if ctx.trace is None:
        return None
    k = ctx.kernel(KERNEL)
    calls = ctx.trace.kernel_calls(k.TRACE_NAMES)
    if not calls:
        return None
    peaks = ctx.peaks()
    least = 0.0
    spent = 0.0
    for seconds, stats in calls:
        shape = k.shape_of(ctx.trace.module_ops.get(stats["module"], ""))
        if shape is None:
            return None
        ops, nbytes = k.cost(shape)
        least += max(ops / peaks[k.PEAK_OPS], nbytes / peaks["hbm_bytes_per_s"])
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None
