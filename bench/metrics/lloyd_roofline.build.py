from bench import peaks, work, work_kmeans


def read(ctx):
    """The fused Lloyd kernel's least time for the builds traced, by the
    work the algorithm needs at unpadded widths (``bench/work_kmeans.py``),
    over the summed device time of the window's Pallas kernel events:
    in a k-means build ``kmeans_assign_update`` is the only Pallas kernel."""
    t = ctx.trace
    if (t is None or not t.kernel_events or t.kernel_sum_s <= 0 or not ctx.completed
            or ctx.config["task"] != "vkmc"):
        return None
    flops, nbytes = work_kmeans.build(ctx.config)
    least, _ = work.least_seconds(flops, nbytes, peaks.peaks_for(ctx.device["kind"]))
    return 100.0 * least * ctx.completed / t.kernel_sum_s
