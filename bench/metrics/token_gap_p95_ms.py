"""95th percentile, over every token emitted in the window, of the time
since the same lane's previous token (host clock, at each decode call's
sync): the gap a streaming reader of a trace sees, set by the ticks that
carry an admission's prefill chunk. A lane's first decode token after
admission has no previous decode token and carries no gap."""
import numpy as np


def read(ctx):
    gaps = ctx.obs.gaps()
    if gaps.size == 0:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
