"""Kernels (the traced part of the window): the held experts' FFN kernel
(``expert_ffn``) inside the decode program. Its least time per run (one
MoE layer of one decode iteration: the larger of the expected routed
pairs' FLOPs over the bf16 peak and, over HBM bandwidth, every held
expert's weights plus the pairs' rows, the family's cost module) over its
measured time per run from the trace, in percent. Absent (None) where the
trace holds no such kernel, as on a program without it."""

KEY = "jit_batched_decode/expert_ffn"


def read(ctx):
    t = ctx.trace
    bursts = ctx.obs.traced()[0]
    if (t is None or not bursts or KEY not in t["kernels"]
            or not hasattr(ctx.cost, "expert_ffn_kernel")):
        return None
    k = t["kernels"][KEY]
    least = 0.0
    for b in bursts:              # per iteration, the burst's mean tokens
        f, by = ctx.cost.expert_ffn_kernel(ctx.model, len(b.lens) / b.steps)
        least += b.steps * max(f / ctx.peaks["bf16_flops_per_s"],
                               by / ctx.peaks["hbm_bytes_per_s"])
    least /= sum(b.steps for b in bursts)     # one layer of one iteration
    return 100.0 * least / (k["s"] / k["count"])
