def read(ctx):
    """Process start to the first timed request."""
    return ctx.setup_s
