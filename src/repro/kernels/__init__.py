"""Pallas TPU kernels for the serving hot spots the paper optimizes.

  flash_attention         — one-shot prefill attention (blockwise
                            online softmax, SWA)
  paged_attention         — decode attention over the paged KV pool
                            (several pages per DMA step; what the fused
                            decode-horizon scan calls)
  paged_attention_prefill — chunked prefill over pooled prefix + exact
                            own-chunk KV (the multi-query face)
  paged_latent_attention  — MLA's absorbed decode over the latent pool
  ssd_scan                — Mamba2 SSD chunked scan (mamba2/zamba2)
  step_score              — fused STEP scorer MLP over decode hiddens

``ops`` also exposes ``paged_attention[_prefill]_sharded`` — the
shard_map routing mesh engines use (lanes on "data", pool KV heads
computed shard-locally on "model").

``ops`` holds the jit'd wrappers (interpret=True on CPU); ``ref`` holds
the pure-jnp oracles the tests assert against.
"""
from repro.kernels import ops, ref  # noqa: F401
