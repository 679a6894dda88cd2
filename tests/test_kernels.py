"""Per-kernel allclose validation vs the pure-jnp oracles, swept over
shapes and dtypes (interpret=True executes the kernel bodies on CPU)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (decode_pages_per_step,
                                           paged_attention,
                                           paged_attention_prefill)
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.step_score import step_score
from repro.models.layers import paged_attention_decode


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,hd,blk", [
    (1, 1, 128, 64, 64),
    (2, 3, 256, 64, 64),
    (1, 2, 256, 128, 128),
    (2, 1, 512, 32, 128),
])
def test_flash_attention_causal(B, H, S, hd, blk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, hd), dtype) for kk in ks)
    out = flash_attention(q, k, v, blk_q=blk, blk_k=blk, interpret=True)
    want = ref.mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_sliding_window(window):
    B, H, S, hd = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    out = flash_attention(q, k, v, window=window, blk_q=64, blk_k=64,
                          interpret=True)
    want = ref.mha_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    B, H, S, hd = 1, 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    out = flash_attention(q, k, v, causal=False, blk_q=64, blk_k=64,
                          interpret=True)
    want = ref.mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,hd,page,bp", [
    (1, 4, 1, 64, 16, 3),     # MQA (granite-style kv=1)
    (3, 8, 2, 64, 16, 4),     # GQA
    (2, 4, 4, 128, 32, 2),    # MHA
    (2, 16, 8, 64, 64, 5),
])
def test_paged_attention(B, H, KVH, hd, page, bp, dtype):
    NB = B * bp + 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k_pool = jax.random.normal(ks[1], (NB, page, KVH, hd), dtype)
    v_pool = jax.random.normal(ks[2], (NB, page, KVH, hd), dtype)
    bt = jax.random.permutation(ks[3], NB)[:B * bp] \
        .reshape(B, bp).astype(jnp.int32)
    lens = jnp.asarray(
        np.random.RandomState(0).randint(1, page * bp + 1, B), jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    out = paged_attention(q, k_pool, v_pool, bt, lens, scale=scale,
                          interpret=True)
    want = ref.paged_attention_ref(
        q.astype(jnp.float32), k_pool.astype(jnp.float32),
        v_pool.astype(jnp.float32), bt, lens, scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_attention_single_token_cache():
    """cache_len=1 edge: only one valid slot."""
    B, H, KVH, hd, page, bp = 1, 2, 1, 32, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pool = jax.random.normal(ks[1], (4, page, KVH, hd))
    v_pool = jax.random.normal(ks[2], (4, page, KVH, hd))
    bt = jnp.array([[1, 2]], jnp.int32)
    lens = jnp.array([1], jnp.int32)
    out = paged_attention(q, k_pool, v_pool, bt, lens, scale=0.2,
                          interpret=True)
    want = ref.paged_attention_ref(q, k_pool, v_pool, bt, lens, scale=0.2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_empty_cache_emits_zeros():
    """The pinned ``cache_len == 0`` convention, identical across the
    kernel, the dense fallback and the oracle: ZEROS. (Previously the
    dense path softmaxed a row of -1e30 fill into a uniform average
    over garbage KV while the kernel emitted zeros — a silent
    use_kernel=True/False divergence for dead decode slots.)"""
    B, H, KVH, hd, page, bp = 2, 4, 2, 32, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pool = jax.random.normal(ks[1], (6, page, KVH, hd))
    v_pool = jax.random.normal(ks[2], (6, page, KVH, hd))
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.array([0, 7], jnp.int32)
    outs = [
        paged_attention(q, k_pool, v_pool, bt, lens, scale=0.2,
                        interpret=True),
        paged_attention_decode(k_pool, v_pool, q, bt, lens, scale=0.2),
        ref.paged_attention_ref(k_pool=k_pool, v_pool=v_pool, q=q,
                                block_tables=bt, cache_lens=lens,
                                scale=0.2),
    ]
    for out in outs:
        out = np.asarray(out, np.float32)
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[0], 0.0)  # empty row -> zeros
        assert np.any(out[1] != 0.0)
    for out in outs[1:]:  # the live row agrees across all three paths
        np.testing.assert_allclose(np.asarray(out, np.float32)[1],
                                   np.asarray(outs[0], np.float32)[1],
                                   rtol=2e-5, atol=2e-5)


def test_dense_decode_f32_accumulation_matches_kernel():
    """bf16 pools: the dense fallback accumulates the PV contraction in
    f32 (it used to cast probs to bf16 first), so use_kernel=True/False
    agree to reduction-order noise — far inside bf16's own rounding."""
    B, H, KVH, hd, page, bp = 2, 8, 2, 64, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (B * bp + 1, page, KVH, hd),
                               jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (B * bp + 1, page, KVH, hd),
                               jnp.bfloat16)
    bt = jnp.arange(1, B * bp + 1, dtype=jnp.int32).reshape(B, bp)
    lens = jnp.array([page * bp, 11], jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    kern = paged_attention(q, k_pool, v_pool, bt, lens, scale=scale,
                           interpret=True)
    dense = paged_attention_decode(k_pool, v_pool, q, bt, lens, scale=scale)
    np.testing.assert_allclose(np.asarray(kern, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.sampled_from((1, 2, 4)),
       st.sampled_from((1, 2, 4)), st.sampled_from((8, 16)),
       st.integers(1, 4), st.integers(0, 10 ** 6))
def test_paged_decode_kernel_vs_dense_property(B, KVH, G, page, bp, seed):
    """Kernel == dense fallback over ragged cache_lens (including empty
    and exactly-full rows — the slot = pos %% window_len wraparound
    regime fills every slot) and GQA group sizes."""
    H = KVH * G
    hd = 32
    NB = B * bp + 2
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pool = jax.random.normal(ks[1], (NB, page, KVH, hd))
    v_pool = jax.random.normal(ks[2], (NB, page, KVH, hd))
    bt = jax.random.permutation(ks[3], NB)[:B * bp] \
        .reshape(B, bp).astype(jnp.int32)
    # ragged: 0 (empty), full (wrapped rolling window), and in-between
    lens = jnp.asarray(
        np.random.RandomState(seed % 2 ** 31).randint(0, page * bp + 1, B),
        jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    kern = paged_attention(q, k_pool, v_pool, bt, lens, scale=scale,
                           interpret=True)
    dense = paged_attention_decode(k_pool, v_pool, q, bt, lens, scale=scale)
    np.testing.assert_allclose(np.asarray(kern, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=2e-5, atol=2e-5)


def _wide_decode_case(B, KVH, G, page, bp, seed, dtype, lens):
    H, hd = KVH * G, 32
    NB = B * bp + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k_pool = jax.random.normal(ks[1], (NB, page, KVH, hd), dtype)
    v_pool = jax.random.normal(ks[2], (NB, page, KVH, hd), dtype)
    bt = jax.random.permutation(ks[3], NB)[:B * bp] \
        .reshape(B, bp).astype(jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    kern = paged_attention(q, k_pool, v_pool, bt, lens, scale=scale,
                           interpret=True)
    dense = paged_attention_decode(k_pool, v_pool, q, bt, lens, scale=scale)
    want = ref.paged_attention_ref(
        q.astype(jnp.float32), k_pool.astype(jnp.float32),
        v_pool.astype(jnp.float32), bt, lens, scale=scale)
    return [np.asarray(x, np.float32) for x in (kern, dense, want)]


@pytest.mark.parametrize("page,bp,KVH,G,dtype", [
    (16, 9, 1, 1, jnp.float32),
    (16, 9, 8, 4, jnp.bfloat16),
    (16, 20, 2, 4, jnp.float32),
    (16, 20, 8, 1, jnp.bfloat16),
    (8, 20, 1, 4, jnp.bfloat16),
    (8, 20, 2, 1, jnp.float32),
    (8, 20, 8, 4, jnp.float32),
    (8, 13, 2, 4, jnp.bfloat16),
])
def test_paged_decode_wide_tables(page, bp, KVH, G, dtype):
    """Block tables wider than one decode step (a step holds
    ``decode_pages_per_step`` pages, 128 tokens here): lengths at 0, 1,
    each step boundary and one either side of it, and a full table, all
    against the dense fallback and the oracle, over MQA, MHA and GQA
    (KVH 1, 2, 8; G 1, 4) and f32 and bf16 pools."""
    rows = decode_pages_per_step(page, bp, 1) * page
    full = page * bp
    cuts = [n for k in range(1, full // rows + 1)
            for n in (k * rows - 1, k * rows, k * rows + 1) if n <= full]
    lens = jnp.asarray([0, 1, *cuts, full], jnp.int32)
    kern, dense, want = _wide_decode_case(len(lens), KVH, G, page, bp, 5,
                                          dtype, lens)
    assert np.all(kern[0] == 0.0) and np.all(np.isfinite(kern))
    np.testing.assert_allclose(kern, want, **_tol(dtype))
    np.testing.assert_allclose(kern, dense, **_tol(dtype))


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 3), st.sampled_from((1, 2, 8)),
       st.sampled_from((1, 4)), st.sampled_from((8, 16)),
       st.integers(9, 20), st.integers(0, 10 ** 6))
def test_paged_decode_kernel_vs_dense_property_wide(B, KVH, G, page, bp,
                                                    seed):
    """The property above over block tables wider than one decode step,
    ragged lengths from empty to full."""
    lens = jnp.asarray(
        np.random.RandomState(seed % 2 ** 31).randint(0, page * bp + 1, B),
        jnp.int32)
    kern, dense, _ = _wide_decode_case(B, KVH, G, page, bp,
                                       seed % (2 ** 31), jnp.float32, lens)
    np.testing.assert_allclose(kern, dense, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("local_kvh", [1, 2])
def test_paged_decode_head_slice_bit_identical(local_kvh):
    """A call over a slice of the kv heads (and their query heads) gives
    that slice of the whole call's output bit for bit, over a wide
    table: what lets the mesh's shard-local call match the single-device
    kernel exactly, checked here without a mesh."""
    KVH, G, hd, page, bp = 2 * local_kvh, 4, 32, 8, 20
    lens = jnp.asarray([0, 1, 127, 128, 129, 160, 77, 150], jnp.int32)
    B, NB = lens.shape[0], lens.shape[0] * bp + 2
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, KVH * G, hd))
    k_pool = jax.random.normal(ks[1], (NB, page, KVH, hd))
    v_pool = jax.random.normal(ks[2], (NB, page, KVH, hd))
    bt = jax.random.permutation(ks[3], NB)[:B * bp] \
        .reshape(B, bp).astype(jnp.int32)
    whole = np.asarray(paged_attention(q, k_pool, v_pool, bt, lens,
                                       scale=0.2, interpret=True))
    for s in range(2):
        heads = slice(s * local_kvh, (s + 1) * local_kvh)
        rows = slice(s * local_kvh * G, (s + 1) * local_kvh * G)
        part = paged_attention(q[:, rows], k_pool[:, :, heads],
                               v_pool[:, :, heads], bt, lens, scale=0.2,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(part), whole[:, rows])


def test_decode_layer_kernel_matches_dense_after_wraparound():
    """Full decode layer at a position past the window: slot =
    pos %% window_len wraps into low blocks; kernel and dense read the
    same rolling window."""
    from repro.configs.registry import serving_config
    from repro.models.init import init_params
    from repro.models.layers import gqa_attention_decode

    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])["attn"]
    B, window_len, bs = 2, 32, cfg.kv_block_size
    bp = window_len // bs
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                (B, 1, cfg.d_model)).astype(jnp.bfloat16)
    positions = jnp.array([window_len + 5, window_len * 2 + 1], jnp.int32)
    pools = {}
    for name, key in (("k_pool", 2), ("v_pool", 3)):
        pools[name] = jax.random.normal(
            jax.random.PRNGKey(key),
            (B * bp + 1, bs, cfg.num_kv_heads, cfg.head_dim),
            jnp.bfloat16)
    bt = jnp.arange(1, B * bp + 1, dtype=jnp.int32).reshape(B, bp)
    outs = {}
    for uk in (False, True):
        cache = {**pools, "block_tables": bt, "window_len": window_len,
                 "use_kernel": uk}
        out, _ = gqa_attention_decode(lp, cfg, x, positions, cache, 0)
        outs[uk] = np.asarray(out, np.float32)
    np.testing.assert_allclose(outs[True], outs[False],
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# multi-query paged attention (chunked prefill)
# ---------------------------------------------------------------------------

def _prefill_case(B, C, KVH, G, page, bp, seed, starts, nvalid):
    H = KVH * G
    hd = 32
    NB = B * bp + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, C, H, hd))
    k_pool = jax.random.normal(ks[1], (NB, page, KVH, hd))
    v_pool = jax.random.normal(ks[2], (NB, page, KVH, hd))
    bt = jax.random.permutation(ks[4], NB)[:B * bp] \
        .reshape(B, bp).astype(jnp.int32)
    own_k = jax.random.normal(ks[3], (B, C, KVH, hd))
    own_v = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (B, C, KVH, hd))
    return (q, k_pool, v_pool, bt, jnp.asarray(starts, jnp.int32),
            jnp.asarray(nvalid, jnp.int32), own_k, own_v)


@pytest.mark.parametrize("window", [None, 9])
def test_paged_prefill_kernel_vs_oracle(window):
    """Chunk boundaries landing mid-page (starts not multiples of the
    page size), ragged validity, first chunk (empty pooled prefix)."""
    B, C, KVH, G, page, bp = 3, 6, 2, 2, 8, 3
    args = _prefill_case(B, C, KVH, G, page, bp, 17,
                         starts=[13, 0, 8], nvalid=[6, 4, 1])
    scale = 1.0 / math.sqrt(32)
    out = paged_attention_prefill(*args, scale=scale, window=window,
                                  interpret=True)
    want = ref.paged_attention_prefill_ref(*args, scale=scale,
                                           window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2), st.sampled_from((3, 4, 8)),
       st.sampled_from((1, 2)), st.sampled_from((1, 4)),
       st.integers(1, 3), st.sampled_from((None, 5)),
       st.integers(0, 10 ** 6))
def test_paged_prefill_kernel_vs_oracle_property(B, C, KVH, G, bp, window,
                                                 seed):
    """Kernel == oracle over random chunk starts (mid-page boundaries),
    ragged num_valid (incl. fully-padded rows) and sliding windows."""
    page = 8
    rs = np.random.RandomState(seed % 2 ** 31)
    max_start = page * bp - 1
    starts = rs.randint(0, max_start + 1, B)
    nvalid = rs.randint(0, C + 1, B)
    args = _prefill_case(B, C, KVH, G, page, bp, seed % 2 ** 31,
                         starts=starts, nvalid=nvalid)
    scale = 1.0 / math.sqrt(32)
    out = paged_attention_prefill(*args, scale=scale, window=window,
                                  interpret=True)
    want = ref.paged_attention_prefill_ref(*args, scale=scale,
                                           window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_prefill_chunk_layer_kernel_matches_dense():
    """The full chunk-prefill layer (KV scatter + attention + output
    projection) agrees between the kernel and dense paths, at a chunk
    boundary landing mid-page."""
    from repro.configs.registry import serving_config
    from repro.models.init import init_params
    from repro.models.layers import gqa_attention_prefill_chunk

    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])["attn"]
    B, C, cap, bs = 1, 5, 64, cfg.kv_block_size
    bp = cap // bs
    start = bs + 3  # mid-page boundary
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                (B, C, cfg.d_model)).astype(jnp.bfloat16)
    positions = start + jnp.arange(C, dtype=jnp.int32)[None, :]
    valid = (jnp.arange(C)[None, :] < 4)
    pools = {
        name: 0.5 * jax.random.normal(
            jax.random.PRNGKey(k),
            (bp + 1, bs, cfg.num_kv_heads, cfg.head_dim)).astype(
                jnp.bfloat16)
        for name, k in (("k", 3), ("v", 4))}
    bt = jnp.arange(1, bp + 1, dtype=jnp.int32)[None, :]
    outs, kps = {}, {}
    for uk in (False, True):
        out, nk, nv = gqa_attention_prefill_chunk(
            lp, cfg, x, positions, valid, pools["k"], pools["v"], bt,
            cap, use_kernel=uk)
        outs[uk] = np.asarray(out[:, :4], np.float32)  # valid region
        kps[uk] = (np.asarray(nk, np.float32), np.asarray(nv, np.float32))
    np.testing.assert_allclose(outs[True], outs[False],
                               rtol=2e-2, atol=2e-2)
    # the pool scatter is path-independent (same written KV bytes)
    for a, b in zip(kps[True], kps[False]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk,g", [
    (1, 64, 2, 8, 16, 16, 1),
    (2, 128, 6, 16, 32, 32, 3),
    (1, 256, 4, 32, 64, 128, 4),
    (2, 96, 5, 16, 32, 32, 4),   # head_group not dividing H -> fallback
])
def test_ssd_scan(B, S, H, P, N, chunk, g):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, head_group=g,
                    interpret=True)
    yr, hr = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=2e-3, atol=2e-3)


def test_ssd_scan_matches_layer_path():
    """Kernel output == the jnp chunked implementation used by models."""
    from repro.models.layers import ssd_chunked
    B, S, H, P, N = 1, 128, 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y_k, h_k = ssd_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    y_j, h_j = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_j),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# step scorer kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,D", [(1, 64), (8, 256), (130, 512), (64, 2560)])
def test_step_score(B, D):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(ks[0], (B, D))
    w1 = jax.random.normal(ks[1], (D, 512)) * 0.05
    b1 = jax.random.normal(ks[2], (512,)) * 0.1
    w2 = jax.random.normal(ks[3], (512, 1)) * 0.05
    b2 = jax.random.normal(ks[4], (1,)) * 0.1
    out = step_score(h, w1, b1, w2, b2, blk_b=64, interpret=True)
    want = ref.step_score_ref(h, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_step_score_matches_scorer_module():
    """Kernel == core.scorer.scorer_score (the engine's fused path)."""
    from repro.core.scorer import init_scorer, scorer_score
    p = init_scorer(jax.random.PRNGKey(1), 128)
    h = jax.random.normal(jax.random.PRNGKey(2), (16, 128))
    out = step_score(h, p["w1"], p["b1"], p["w2"], p["b2"], interpret=True)
    want = scorer_score(p, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ops wrappers (CPU => interpret) usable inside the model path
# ---------------------------------------------------------------------------

def test_ops_interpret_on_cpu():
    assert jax.default_backend() == "cpu"
    B, H, S, hd = 1, 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    out = ops.flash_attention(q, k, v)
    want = ref.mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
