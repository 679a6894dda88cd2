"""Device: the share of the traced part of the window in which no operation
ran on the chip (1 - union of op intervals / window), in percent."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
