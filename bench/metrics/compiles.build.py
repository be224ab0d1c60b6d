def read(ctx):
    """Executables compiled or read from the cache inside the window."""
    return ctx.compiles
