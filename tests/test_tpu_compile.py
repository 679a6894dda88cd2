"""The main path's Pallas kernels compile for a TPU v5e at Qwen3-4B widths,
and the latent face and the expert FFN at DeepSeek-V2-Lite's.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned slices, loads from HBM refs, more VMEM than a
kernel may use. These tests compile each kernel ahead of time against a
described ``v5e:2x2`` topology — no chip is needed, nothing runs — and
check that the program holds the Mosaic kernel (``tpu_custom_call``)
rather than a fallback. The decode program's sampler compiles the same
way, to check that no sort over the whole vocabulary comes back, and so
does the Qwen3 cell's whole decode program, to check how it runs the
paged-attention kernel.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and a
fixture keeps the load in the one worker that runs this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import deepseek_v2_lite
from repro.configs.qwen3_4b_thinking import full_config
from repro.core.scorer import SCORER_HIDDEN
from repro.kernels import expert_ffn as ef
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


def _decode_cell(sds, pool_dtype):
    """The decode face at the Qwen3 cell's shapes: 128 lanes of 4,608
    tokens (288 pages) over one layer's pool of 1,664 pages."""
    cell_blocks = 1664
    pools = [sds((cell_blocks, PAGE, KVH, HD), pool_dtype)] * 2
    kw = {}
    if pool_dtype == jnp.int8:
        kw = dict(k_scale=sds((cell_blocks, PAGE, KVH), jnp.float32),
                  v_scale=sds((cell_blocks, PAGE, KVH), jnp.float32))
    fn = jax.jit(lambda q, kp, vp, bt, lens, **kw: pa.paged_attention(
        q, kp, vp, bt, lens, scale=HD ** -0.5, interpret=False, **kw))
    return fn.lower(sds((128, H, HD), jnp.bfloat16), *pools,
                    sds((128, 4608 // PAGE), jnp.int32),
                    sds((128,), jnp.int32), **kw)


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


MLA = dataclasses.replace(deepseek_v2_lite.full_config(), experts_held=16)


def _latent(sds, _):
    """The latent face at 128 lanes of 4,608 tokens over a 27-layer pool
    of 6,500 pages, entries of kv_lora 512 + rope 64 padded to 640."""
    H, W = MLA.num_heads, MLA.latent_width
    bp = 4608 // PAGE
    fn = jax.jit(lambda q, pool, layer, bt, pos, own:
                 pa.paged_latent_attention(
                     q, pool, layer, bt, pos, own, scale=0.1, window=4608,
                     out_dim=MLA.kv_lora_rank, interpret=False))
    return fn.lower(sds((128, H, W), jnp.bfloat16),
                    sds((MLA.num_layers, 6500, PAGE, W), jnp.bfloat16),
                    sds((), jnp.int32), sds((128, bp), jnp.int32),
                    sds((128,), jnp.int32), sds((128, W), jnp.bfloat16))


def _experts(sds, _):
    """The expert FFN over 16 held experts of 26 MoE layers, the row
    tiles of 128 tokens' top-6 pairs."""
    D, F, Eh = MLA.d_model, MLA.moe_d_ff, MLA.num_experts_held
    Lm, tile = MLA.num_moe_layers, 16
    n_tiles = -(-(128 * 6 + Eh * (tile - 1)) // tile)
    fn = jax.jit(lambda x, wg, wu, wd, layer, te, nr: ef.expert_ffn(
        x, wg, wu, wd, layer, te, nr, tile=tile, interpret=False))
    return fn.lower(sds((n_tiles * tile, D), jnp.bfloat16),
                    sds((Lm, Eh, D, F), jnp.bfloat16),
                    sds((Lm, Eh, D, F), jnp.bfloat16),
                    sds((Lm, Eh, F, D), jnp.bfloat16),
                    sds((), jnp.int32), sds((n_tiles,), jnp.int32),
                    sds((1,), jnp.int32))


@pytest.mark.parametrize("lower, pool_dtype", [
    (_decode, jnp.bfloat16),
    (_decode, jnp.int8),
    (_prefill, jnp.bfloat16),
    (_prefill, jnp.int8),
    (_step_score, None),
    (_latent, jnp.bfloat16),
    (_experts, None),
    (_decode_cell, jnp.bfloat16),
    (_decode_cell, jnp.int8),
], ids=["decode_bf16", "decode_int8", "prefill_bf16", "prefill_int8",
        "step_score", "latent_decode", "expert_ffn", "decode_cell_bf16",
        "decode_cell_int8"])
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


def test_decode_program_runs_one_kernel_per_layer(one_chip, monkeypatch):
    """The Qwen3 cell's decode program (``jit_batched_decode``, 128
    lanes, 1,664 pages, 36 layers) holds one Mosaic kernel whose op name
    is ``paged_attention``, and it reads one layer's pools as the layer
    scan's body updated them: no copy, transpose or relayout of a
    layer's pool. The trace reduction times that op by its name, and
    ``paged_decode_roofline`` divides its time by its run count."""
    import collections
    import json
    import re

    from bench import harness, trace_reduce
    from bench.families.qwen3 import weights
    from repro.core.pruning import make_policy
    from repro.data.tokenizer import get_tokenizer
    from repro.kernels import ops as kops
    from repro.serving import Engine, EngineConfig, SamplingParams

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    bench_dir = os.path.join(os.path.dirname(__file__), "..", "bench")

    def load(*path):
        with open(os.path.join(bench_dir, *path)) as f:
            return json.load(f)

    config = load("configs", "qwen3-4b-thinking.json")
    mix = load("traffic", "step-pressure.json")
    cfg, m = harness.model_config(config), config["model"]

    def put(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params, scorer = put(jax.eval_shape(
        lambda k: weights.make_weights(tuple(sorted(m.items())), k,
                                       get_tokenizer().step_id,
                                       config["step_row_scale"]),
        jax.random.PRNGKey(0)))
    e = {**mix["engine"], "use_kernel": True}
    samp = SamplingParams(**mix["sampling"], max_new_tokens=e["capacity"])
    eng = Engine(params, cfg,
                 EngineConfig(**e, num_blocks=config["num_blocks"],
                              max_new_tokens=e["capacity"], sampling=samp,
                              faults=None),
                 make_policy(mix["policy"]), scorer_params=scorer)
    cache = put(jax.eval_shape(eng._init_cache))
    B, bps = eng.ecfg.max_batch, eng.blocks_per_seq

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = eng.decode_fn(1).lower(
        params, cache, i32(B), i32(B), i32(B), i32(B, bps), rng,
        scorer).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = [trace_reduce.op_base(ln.strip()) for ln in calls]
    assert names.count("paged_attention") == 1, names
    # its pool operands are one layer's slice: the kernel runs once per
    # iteration of the layer scan
    layer = f"[{config['num_blocks']},{PAGE},{KVH},{HD}]"
    kernel = calls[names.index("paged_attention")]
    assert kernel.split("operand_layout_constraints={")[1].count(
        layer) == 2
    # every op of a layer slice's size: the scan's slice of the stacked
    # pools, the new token's scatter, and the bitcasts between them
    ops = collections.Counter(
        re.match(r"\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(", ln).group(1)
        for ln in text.splitlines()
        if re.match(rf"\s*(?:ROOT )?%\S+ = bf16{re.escape(layer)}[{{ ]",
                    ln))
    assert ops == {"parameter": 4, "bitcast": 2, "scatter": 2,
                   "fusion": 4}, ops
