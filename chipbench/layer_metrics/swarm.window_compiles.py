"""Runtime: programs compiled or loaded from the compilation cache
inside the measured window, from JAX's monitoring events."""


def read(ctx):
    c = ctx.stats.get("compiles")
    return None if c is None else float(c)
