"""Kernels (the traced part of the window): the latent paged-attention
kernel inside the decode program. Its least time per run (one layer of
one decode iteration: the larger of FLOPs over the bf16 peak and live
latent-page bytes over HBM bandwidth, the family's cost module) over its
measured time per run from the trace, in percent. A decode call of K
iterations runs the kernel K times per layer. Absent (None) where the
trace holds no such kernel, as on a program without the latent face."""

KEY = "jit_batched_decode/paged_latent_attention"


def read(ctx):
    t = ctx.trace
    bursts = ctx.obs.traced()[0]
    if (t is None or not bursts or KEY not in t["kernels"]
            or not hasattr(ctx.cost, "latent_decode_kernel")):
        return None
    k = t["kernels"][KEY]
    least = 0.0
    for b in bursts:
        f, by = ctx.cost.latent_decode_kernel(ctx.model, b.lens, ctx.page)
        least += max(f / ctx.peaks["bf16_flops_per_s"],
                     by / ctx.peaks["hbm_bytes_per_s"])
    least /= sum(b.steps for b in bursts)     # one layer of one iteration
    return 100.0 * least / (k["s"] / k["count"])
