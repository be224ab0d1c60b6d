from bench import peaks, work


def read(ctx):
    """The score kernels' least time for the builds traced, by the work the
    algorithm needs at unpadded widths, over their summed device time."""
    t = ctx.trace
    if t is None or not t.kernel_events or t.kernel_sum_s <= 0 or not ctx.completed:
        return None
    flops, nbytes = work.score_pass(ctx.config)
    least, _ = work.least_seconds(flops, nbytes, peaks.peaks_for(ctx.device["kind"]))
    return 100.0 * least * ctx.completed / t.kernel_sum_s
