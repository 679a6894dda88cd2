"""The main path's Pallas kernels compile for a TPU v5e at Qwen3-4B widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned slices, loads from HBM refs, more VMEM than a
kernel may use. These tests compile each kernel ahead of time against a
described ``v5e:2x2`` topology — no chip is needed, nothing runs — and
check that the program holds the Mosaic kernel (``tpu_custom_call``)
rather than a fallback. The decode program's sampler compiles the same
way, to check that no sort over the whole vocabulary comes back.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and a
fixture keeps the load in the one worker that runs this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen3_4b_thinking import full_config
from repro.core.scorer import SCORER_HIDDEN
from repro.kernels import paged_attention as pa
from repro.kernels import step_score as ss
from repro.models.init import padded_vocab
from repro.serving.sampling import sample_logits

CFG = full_config()
H, KVH, HD, PAGE = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim, 16
B, NUM_BLOCKS, PAGES_PER_SEQ, CHUNK = 16, 512, 64, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache.compilation_cache import \
        reset_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    reset_cache()


def _pool_args(sds, pool_dtype):
    pools = [sds((NUM_BLOCKS, PAGE, KVH, HD), pool_dtype)] * 2
    kw = {}
    if pool_dtype == jnp.int8:
        kw = dict(k_scale=sds((NUM_BLOCKS, PAGE, KVH), jnp.float32),
                  v_scale=sds((NUM_BLOCKS, PAGE, KVH), jnp.float32))
    return pools, sds((B, PAGES_PER_SEQ), jnp.int32), kw


def _decode(sds, pool_dtype):
    pools, bt, kw = _pool_args(sds, pool_dtype)
    fn = jax.jit(lambda q, kp, vp, bt, lens, **kw: pa.paged_attention(
        q, kp, vp, bt, lens, scale=HD ** -0.5, interpret=False, **kw))
    return fn.lower(sds((B, H, HD), jnp.bfloat16), *pools, bt,
                    sds((B,), jnp.int32), **kw)


def _prefill(sds, pool_dtype):
    pools, bt, kw = _pool_args(sds, pool_dtype)
    own = sds((B, CHUNK, KVH, HD), jnp.bfloat16)
    fn = jax.jit(lambda q, kp, vp, bt, pl_, nv, ok, ov, **kw:
                 pa.paged_attention_prefill(q, kp, vp, bt, pl_, nv, ok, ov,
                                            scale=HD ** -0.5,
                                            interpret=False, **kw))
    return fn.lower(sds((B, CHUNK, H, HD), jnp.bfloat16), *pools, bt,
                    sds((B,), jnp.int32), sds((B,), jnp.int32), own, own,
                    **kw)


def _step_score(sds, _):
    D = CFG.d_model
    fn = jax.jit(lambda h, w1, b1, w2, b2: ss.step_score(
        h, w1, b1, w2, b2, interpret=False))
    return fn.lower(sds((B, D), jnp.bfloat16),
                    sds((D, SCORER_HIDDEN), jnp.float32),
                    sds((SCORER_HIDDEN,), jnp.float32),
                    sds((SCORER_HIDDEN, 1), jnp.float32),
                    sds((1,), jnp.float32))


@pytest.mark.parametrize("lower, pool_dtype", [
    (_decode, jnp.bfloat16),
    (_decode, jnp.int8),
    (_prefill, jnp.bfloat16),
    (_prefill, jnp.int8),
    (_step_score, None),
], ids=["decode_bf16", "decode_int8", "prefill_bf16", "prefill_int8",
        "step_score"])
def test_kernel_compiles_for_v5e(one_chip, lower, pool_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = lower(sds, pool_dtype).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sampler_sorts_no_vocabulary_row(one_chip):
    """The engine's top-k/top-p sampler at 128 lanes of Qwen3-4B's padded
    vocabulary: the compiler may turn a ``lax.top_k`` back into a sort
    of its whole input, so no ``sort`` may take a vocabulary-wide
    operand."""
    vp = padded_vocab(CFG)
    fn = jax.jit(lambda k, x: sample_logits(k, x, temperature=0.6,
                                            top_k=20, top_p=0.95))
    text = fn.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((128, vp), jnp.bfloat16, sharding=one_chip),
    ).compile().as_text()
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert sorts, "the selection's small sorts are gone: check the pattern"
    assert not [ln for ln in sorts if f",{vp}]" in ln.split(" sort(")[0]]
