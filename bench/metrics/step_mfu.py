"""Jitted steps: model FLOPs of every token the decode and prefill
programs computed in the window (by shape and context, the family's cost
module), over the window's seconds times the chip's bf16 peak, in
percent."""


def read(ctx):
    m, obs, cost = ctx.model, ctx.obs, ctx.cost
    total = sum(cost.token_flops(m, n) for b in obs.bursts for n in b.lens)
    total += sum(cost.prefill_flops(m, start, n)
                 for _, start, n in obs.chunks)
    if not total:
        return None
    return 100.0 * total / (ctx.seconds * ctx.peaks["bf16_flops_per_s"])
