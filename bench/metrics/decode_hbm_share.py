"""Jitted steps (the traced part of the window): the bytes one decode call
must move (the weights once per decode iteration, every emitted token's
live KV pages in every layer) over the decode program's device time per
call from the trace, over the chip's HBM bandwidth, in percent."""

PROGRAM = "jit_batched_decode"


def read(ctx):
    t = ctx.trace
    bursts = ctx.obs.traced()[0]
    if t is None or not bursts or PROGRAM not in t["modules"]:
        return None
    mod = t["modules"][PROGRAM]
    per_call = sum(ctx.cost.decode_call_bytes(ctx.model, ctx.weight_bytes,
                                              b.lens, ctx.page, b.steps)
                   for b in bursts) / len(bursts)
    secs = mod["s"] / mod["count"]
    return 100.0 * per_call / secs / ctx.peaks["hbm_bytes_per_s"]
