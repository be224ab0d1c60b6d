def read(ctx):
    """Share of the traced window covered by Pallas (Mosaic) kernels."""
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.kernel_events:
        return None
    return 100.0 * t.kernel_union_s / t.window_s
