"""Quantized paged-KV pool: round-trip bounds, read-path alignment,
engine-level dtype contracts, byte accounting, and the fused scorer.

The exactness contract (module docstring of ``repro.models.kv_quant``):

- ``bf16`` vs ``f32`` pools are ENGINE-IDENTICAL (tokens, step scores,
  confidences, prune decisions) — activations are bf16, so an f32 pool
  stores the same values a bf16 pool does, just wider.
- ``int8``/``fp8`` pools get BOUNDED-DRIFT guarantees: per-element
  round-trip error within the scale-derived bound, attention outputs
  within a small relative drift of the float-pool result, and the
  engine still serves/prunes/drains correctly.
- The Pallas kernel's in-loop dequant matches the dense fallback's
  gathered dequant (same codes, same bf16-grid scales, same f32 math).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.scorer import init_scorer, scorer_score
from repro.data.tokenizer import get_tokenizer
from repro.kernels import ops as kops
from repro.models import kv_quant
from repro.models.init import init_params
from repro.serving import Engine, EngineConfig, SamplingParams

# ---------------------------------------------------------------------------
# quantize/dequantize properties
# ---------------------------------------------------------------------------

# int-grid floats keep the stub-compatible strategy surface (no floats()):
# value = mantissa * 2^exp spans several binades with exact inputs
_mantissa = st.integers(min_value=-4096, max_value=4096)
_exp = st.integers(min_value=-4, max_value=4)


@st.composite
def _vectors(draw):
    hd = draw(st.sampled_from([4, 8, 16]))
    rows = draw(st.integers(min_value=1, max_value=5))
    e = draw(_exp)
    vals = [draw(_mantissa) for _ in range(rows * hd)]
    x = np.asarray(vals, np.float32).reshape(rows, hd) * (2.0 ** e)
    return x


@settings(max_examples=25, deadline=None)
@given(_vectors(), st.booleans())
def test_quantize_roundtrip_bounded(x, use_int8):
    """Per-element round-trip error stays under the scale-derived bound:
    ~scale/2 (+ bf16-scale-grid slack) for int8, ~2^-4 relative for
    fp8's 3-bit mantissa. Zero vectors stay exactly zero at scale 1."""
    qdtype = jnp.int8 if use_int8 else jnp.float8_e4m3fn
    q, scale = kv_quant.quantize_pages(jnp.asarray(x), qdtype)
    rt = np.asarray(kv_quant.dequantize_pages(q, scale))
    absmax = np.max(np.abs(x), axis=-1, keepdims=True)
    s = np.asarray(scale)[..., None]
    if use_int8:
        bound = 1.5 * s  # round-to-nearest + bf16 scale grid + clip edge
    else:
        bound = 0.07 * absmax + 1e-7
    assert np.all(np.abs(rt - x) <= bound)
    zero_rows = absmax[..., 0] == 0.0
    assert np.all(np.asarray(scale)[zero_rows] == 1.0)
    assert np.all(rt[zero_rows] == 0.0)


@settings(max_examples=25, deadline=None)
@given(_vectors())
def test_quantize_is_per_slot_pure(x):
    """A slot's codes and scale depend only on its own vector: quantizing
    row-by-row matches quantizing the batch — the property that makes
    every pool write path (one-shot, chunked, decode, COW) commute."""
    q_all, s_all = kv_quant.quantize_pages(jnp.asarray(x), jnp.int8)
    for i in range(x.shape[0]):
        q_i, s_i = kv_quant.quantize_pages(jnp.asarray(x[i:i + 1]),
                                           jnp.int8)
        assert np.array_equal(np.asarray(q_all[i:i + 1]), np.asarray(q_i))
        assert np.array_equal(np.asarray(s_all[i:i + 1]), np.asarray(s_i))


def test_scales_live_on_bf16_grid():
    """Stored scales are bf16-representable f32 — the property that keeps
    ``code * scale`` exact in f32 and the kernel/dense read paths
    bit-aligned (see quantize_pages docstring)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64), jnp.float32)
    _, scale = kv_quant.quantize_pages(x, jnp.int8)
    assert scale.dtype == jnp.float32
    assert np.array_equal(
        np.asarray(scale),
        np.asarray(scale.astype(jnp.bfloat16).astype(jnp.float32)))


# ---------------------------------------------------------------------------
# dtype registry / gating / byte accounting
# ---------------------------------------------------------------------------

def test_resolve_kv_dtype_gating():
    cfg = serving_config()
    for dt in ("f32", "bf16"):
        assert kv_quant.resolve_kv_dtype(dt, cfg, False) == dt
    assert kv_quant.resolve_kv_dtype("int8", cfg, True) == "int8"
    with pytest.raises(NotImplementedError, match="SUPPORT_MATRIX"):
        kv_quant.resolve_kv_dtype("int8", cfg, False)
    with pytest.raises(ValueError, match="kv_dtype"):
        kv_quant.resolve_kv_dtype("int4", cfg, True)
    assert kv_quant.resolve_kv_dtype("fp8", cfg, True) == "fp8"


def test_pool_block_bytes_ordering():
    cfg = serving_config()
    b = {dt: kv_quant.pool_block_bytes(cfg, dt)
         for dt in ("f32", "bf16", "int8")}
    assert b["f32"] == 2 * b["bf16"]
    # int8 pays half of bf16 plus the per-slot f32 scales (1/head_dim
    # of the f32 pool bytes per K/V)
    la = len(cfg.attention_layer_ids())
    scales = la * 2 * cfg.kv_block_size * cfg.num_kv_heads * 4
    assert b["int8"] == b["bf16"] // 2 + scales
    assert b["int8"] < b["bf16"] < b["f32"]


def test_engine_byte_accounting():
    """BlockManager carries pool_block_bytes into AdmissionPressure so
    the scheduler's admission math can reason in HBM bytes."""
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, _ecfg(kv_dtype="int8"), make_policy("sc"))
    from repro.core.pruning import AdmissionPressure
    expect = kv_quant.pool_block_bytes(cfg, "int8")
    assert eng.kv_block_bytes == expect
    assert eng.block_mgr.bytes_per_block == expect
    assert eng.block_mgr.free_bytes \
        == eng.block_mgr.free_blocks * expect
    p = AdmissionPressure(waiting_traces=0, queued_requests=0,
                          free_blocks=eng.block_mgr.free_blocks,
                          total_blocks=10, cached_blocks=2,
                          evictable_blocks=2, bytes_per_block=expect)
    assert p.total_bytes == 10 * expect
    assert p.free_bytes == eng.block_mgr.free_blocks * expect
    assert p.reclaimable_bytes == (eng.block_mgr.free_blocks + 2) * expect


# ---------------------------------------------------------------------------
# kernel-vs-dense read-path alignment (op level)
# ---------------------------------------------------------------------------

def _quantized_pool(key, nb, page, kvh, hd, qdtype):
    x = jax.random.normal(key, (nb, page, kvh, hd), jnp.float32)
    q, s = kv_quant.quantize_pages(x, qdtype)
    return x, q, s


def _kernel_vs_dense_dequant(qdtype, nb, page, kvh, hd, H, bt, lens):
    """Kernel output and the dense path's gathered dequant (the same codes
    times the same scales) for one quantized pool."""
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(3), 3)
    B = bt.shape[0]
    _, kq, ks = _quantized_pool(k0, nb, page, kvh, hd, qdtype)
    _, vq, vs = _quantized_pool(k1, nb, page, kvh, hd, qdtype)
    q = jax.random.normal(k2, (B, H, hd), jnp.float32)
    scale = 1.0 / np.sqrt(hd)

    out = kops.paged_attention(q, kq, vq, bt, lens, scale=scale,
                               k_scale=ks, v_scale=vs)

    kf = kv_quant.dequantize_pages(kq, ks)[bt].reshape(B, -1, kvh, hd)
    vf = kv_quant.dequantize_pages(vq, vs)[bt].reshape(B, -1, kvh, hd)
    G = H // kvh
    qg = q.reshape(B, kvh, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, kf,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(kf.shape[1])[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None], p, 0.0)   # empty rows emit zeros
    ref = jnp.einsum("bkgs,bskh->bkgh", p, vf).reshape(B, H, hd)
    return np.asarray(out), np.asarray(ref)


def test_kernel_decode_matches_dense_dequant():
    """The kernel's in-loop dequant reproduces the dense path's gathered
    dequant: same codes * same scales -> same f32 operands, outputs
    equal to reduction-order noise."""
    bt = jnp.arange(3 * 2, dtype=jnp.int32).reshape(3, 2)
    out, ref = _kernel_vs_dense_dequant(
        jnp.int8, 8, 4, 2, 16, 4, bt, jnp.array([3, 8, 5], jnp.int32))
    np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
def test_kernel_decode_matches_dense_dequant_wide(qdtype):
    """The same over a block table wider than one decode step (16 pages
    of 8 tokens a step, 20 pages a lane), lengths at 0, 1, the step
    boundary and one either side of it, and a full table."""
    lens = jnp.array([0, 1, 127, 128, 129, 160], jnp.int32)
    B, bp = lens.shape[0], 20
    bt = jax.random.permutation(jax.random.PRNGKey(5), B * bp + 2)[
        :B * bp].reshape(B, bp).astype(jnp.int32)
    out, ref = _kernel_vs_dense_dequant(qdtype, B * bp + 2, 8, 2, 16, 8,
                                        bt, lens)
    np.testing.assert_array_equal(out[0], 0.0)
    np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)


def test_quantized_attention_drift_bounded():
    """int8 pool attention stays within a small relative drift of the
    float-pool result — the op-level bound behind the engine-level
    bounded-drift contract."""
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(4), 3)
    nb, page, kvh, hd, B, H = 8, 4, 2, 16, 3, 4
    kx, kq, ks = _quantized_pool(k0, nb, page, kvh, hd, jnp.int8)
    vx, vq, vs = _quantized_pool(k1, nb, page, kvh, hd, jnp.int8)
    q = jax.random.normal(k2, (B, H, hd), jnp.float32)
    bt = jnp.arange(B * 2, dtype=jnp.int32).reshape(B, 2)
    lens = jnp.array([3, 8, 5], jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    out_q = kops.paged_attention(q, kq, vq, bt, lens, scale=scale,
                                 k_scale=ks, v_scale=vs)
    out_f = kops.paged_attention(q, kx, vx, bt, lens, scale=scale)
    diff = np.abs(np.asarray(out_q) - np.asarray(out_f)).max()
    assert diff < 0.05 * np.abs(np.asarray(out_f)).max()


# ---------------------------------------------------------------------------
# engine-level dtype contracts
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scorer = init_scorer(jax.random.PRNGKey(1), cfg.d_model)
    tok = get_tokenizer()
    return cfg, params, scorer, tok


def _ecfg(kv_dtype="bf16", num_blocks=40, max_new=24, chunk=None,
          K=1, temperature=0.0, use_kernel=False, prefix_cache=False):
    return EngineConfig(
        max_batch=8, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new,
        sampling=SamplingParams(
            temperature=temperature,
            top_k=0 if temperature == 0.0 else 20,
            top_p=1.0 if temperature == 0.0 else 0.95,
            max_new_tokens=max_new),
        prefill_chunk_size=chunk, decode_horizon=K,
        use_kernel=use_kernel, kv_dtype=kv_dtype,
        share_prompt_prefix=prefix_cache, prefix_cache=prefix_cache)


def _serve(setup, seed=7, n=4, prompt="3+5-2=", **kw):
    cfg, params, scorer, tok = setup
    eng = Engine(params, cfg, _ecfg(**kw), make_policy("step"),
                 scorer_params=scorer)
    eng._rng = jax.random.PRNGKey(seed)
    res = eng.serve(tok.encode(prompt, add_bos=True), n)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
    return eng, res


def test_engine_bf16_f32_identical(setup):
    """bf16 and f32 pools serve IDENTICAL results: activations are bf16,
    so the f32 pool stores exactly the values the bf16 pool does."""
    runs = {}
    for dt in ("bf16", "f32"):
        _, res = _serve(setup, kv_dtype=dt, temperature=0.8, chunk=4, K=2)
        runs[dt] = [(t.output_tokens, t.step_scores, t.token_confidences,
                     t.status) for t in res.traces]
    assert runs["bf16"] == runs["f32"]


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_engine_quantized_bounded_drift(setup, kv_dtype):
    """Quantized pools: the engine still serves end-to-end (greedy decode,
    chunked prefill, scorer, pruning bookkeeping) and its step scores
    stay within a loose drift band of the float-pool run — the engine
    face of the op-level 5% attention bound."""
    _, res_f = _serve(setup, kv_dtype="f32")
    _, res_q = _serve(setup, kv_dtype=kv_dtype)
    assert len(res_q.traces) == len(res_f.traces)
    for tq, tf in zip(res_q.traces, res_f.traces):
        assert len(tq.output_tokens) > 0
        for sq, sf in zip(tq.step_scores, tf.step_scores):
            assert abs(sq - sf) < 0.25


def test_engine_int8_kernel_path_smoke(setup):
    """Quantized pool + Pallas kernel path (in-kernel dequant) + chunked
    prefill + decode horizon all compose; tokens match the quantized
    dense path exactly (decode face is bit-aligned, greedy sampling)."""
    _, res_d = _serve(setup, kv_dtype="int8", use_kernel=False, K=2,
                      chunk=4)
    _, res_k = _serve(setup, kv_dtype="int8", use_kernel=True, K=2,
                      chunk=4)
    assert [t.output_tokens for t in res_d.traces] \
        == [t.output_tokens for t in res_k.traces]
    assert [t.status for t in res_d.traces] \
        == [t.status for t in res_k.traces]


def test_prefix_cache_serves_quantized_blocks(setup):
    """Scales travel with parked blocks: a warm-cache replay under int8
    hits the radix tree, serves from quantized parked KV, and drains
    cleanly with allocator integrity intact."""
    cfg, params, scorer, tok = setup
    eng = Engine(params, cfg,
                 _ecfg(kv_dtype="int8", num_blocks=24, prefix_cache=True),
                 make_policy("step"), scorer_params=scorer)
    prompt = tok.encode("1+2-3+4-5+6-7+8=" * 2, add_bos=True)
    rounds = []
    for _ in range(2):
        res = eng.serve(prompt, 4)
        rounds.append([t.output_tokens for t in res.traces])
    assert eng.prefix_cache is not None
    assert eng.prefix_cache.stats.hits > 0
    # warm replay reads the same quantized prefix KV -> same greedy tokens
    assert rounds[0] == rounds[1]
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
    eng.prefix_cache.check_integrity()


# ---------------------------------------------------------------------------
# fused step scorer
# ---------------------------------------------------------------------------

def test_fused_scorer_matches_dense_scorer(setup):
    """The Pallas step_score kernel computes the scorer_score graph (f32
    matmuls, ReLU, sigmoid); only matmul reduction order differs, so
    outputs agree to f32 ulps on arbitrary hiddens (the engine-level
    test below pins exact equality on real decode hiddens)."""
    cfg, _, scorer, _ = setup
    h = jax.random.normal(jax.random.PRNGKey(5), (16, cfg.d_model),
                          jnp.bfloat16)
    fused = kops.step_score_params(h, scorer)
    dense = scorer_score(scorer, h)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               atol=2e-6, rtol=0)


def test_fused_scorer_engages_on_kernel_path(setup):
    """use_kernel=True fuses the scorer into the decode burst; the dense
    engine keeps the separate pass. Scores stay identical either way
    (the engine-level fused-vs-separate identity pin)."""
    cfg, params, scorer, tok = setup
    engines = {}
    for uk in (False, True):
        eng = Engine(params, cfg, _ecfg(use_kernel=uk, K=2),
                     make_policy("step"), scorer_params=scorer)
        engines[uk] = eng
    assert engines[False].fused_scorer is False
    assert engines[True].fused_scorer is True
    results = {}
    for uk, eng in engines.items():
        eng._rng = jax.random.PRNGKey(11)
        res = eng.serve(tok.encode("3+5-2=", add_bos=True), 4)
        results[uk] = [t.step_scores for t in res.traces]
    assert results[False] == results[True]


def test_no_scorer_no_fusion(setup):
    cfg, params, _, _ = setup
    eng = Engine(params, cfg, _ecfg(use_kernel=True), make_policy("sc"))
    assert eng.fused_scorer is False
