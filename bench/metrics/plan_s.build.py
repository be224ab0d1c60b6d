def read(ctx):
    """Host seconds in ``CoresetPipeline.plan`` per build of the window."""
    spans = ctx.spans.in_window("plan")
    return sum(spans) / ctx.completed if ctx.completed and spans else None
