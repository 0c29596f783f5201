"""compile: programs newly compiled or loaded from the cache inside the window."""


def read(ctx):
    return ctx.window_compiles
