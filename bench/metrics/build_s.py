def read(ctx):
    """The window's seconds over the builds completed in it."""
    return ctx.window_s / ctx.completed if ctx.completed else None
