"""Scheduler: mean over the window's decode calls of running lanes over
decode slots, in percent."""


def read(ctx):
    b = ctx.obs.bursts
    if not b:
        return None
    return 100.0 * sum(x.n_lanes for x in b) / len(b) / ctx.engine["max_batch"]
