"""Backend compiles that ended inside the window (JAX's compile events)."""


def read(ctx):
    return ctx.compiles["backend_compiles"]
