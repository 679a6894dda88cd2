"""Process start until the window opens: imports, weights, engine, warm-up
from the compile cache, and the ramp the cell's traffic needs."""


def read(ctx):
    return ctx.setup_s
