"""Decode path == full forward path, for every architecture family.

Prefill S tokens (forward_full + write_prefill_kv), then decode token S and
compare logits against forward_full run on the full S+1 sequence. This is
the core invariant the serving engine relies on. The fused decode horizon
(``multi_decode_step``) must equal the single steps it fuses, and the
chunked / rematerialised full-forward paths their plain versions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ALL_ARCHS, get_config
from repro.models import layers as L
from repro.models.init import init_params
from repro.models.model import (build_cross_cache, decode_step, encode,
                                forward_full, init_decode_cache,
                                multi_decode_step, write_prefill_kv)

S = 33  # deliberately not a multiple of block size or ssm chunk
B = 2
CAPACITY = 64


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    rng = jax.random.PRNGKey(42)
    params = init_params(cfg, rng)
    tokens = jax.random.randint(rng, (B, S + 5), 0, cfg.vocab_size)
    kw = {}
    if cfg.modality == "vision":
        kw["modality_embeds"] = 0.02 * jax.random.normal(
            rng, (B, cfg.num_modality_tokens, cfg.d_model)).astype(jnp.bfloat16)
    if cfg.is_encoder_decoder:
        kw["encoder_embeds"] = 0.02 * jax.random.normal(
            rng, (B, cfg.encoder_seq_len, cfg.d_model)).astype(jnp.bfloat16)
    return cfg, params, tokens, kw


def _prefilled_cache(cfg, params, tokens, kw):
    """The decode cache after a prefill of the first S tokens."""
    out = forward_full(params, cfg, tokens[:, :S], return_kv=True, **kw)
    cache = init_decode_cache(cfg, B, CAPACITY)
    cache = write_prefill_kv(cfg, cache, out["kvs"],
                             jnp.full((B,), S, jnp.int32))
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, kw["encoder_embeds"])
        cache["cross_k"], cache["cross_v"] = build_cross_cache(
            params, cfg, enc_out)
    return cache


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_matches_full_forward(arch):
    cfg, params, tokens, kw = _setup(arch)

    # reference: full forward over S+1 tokens
    ref = forward_full(params, cfg, tokens[:, :S + 1], **kw)
    ref_logits = np.asarray(ref["logits"][:, S].astype(jnp.float32))

    # prefill S tokens, capture kv/state
    cache = _prefilled_cache(cfg, params, tokens, kw)

    step = decode_step(params, cfg, tokens[:, S:S + 1],
                       jnp.full((B,), S, jnp.int32), cache,
                       window_len=CAPACITY)
    got = np.asarray(step["logits"].astype(jnp.float32))

    np.testing.assert_allclose(got, ref_logits, rtol=0.08, atol=0.08)
    assert np.all(np.isfinite(got))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-2.7b", "zamba2-2.7b"])
def test_multi_step_decode(arch):
    """Decode 4 consecutive tokens; each must match the full forward."""
    cfg, params, tokens, kw = _setup(arch)
    out = forward_full(params, cfg, tokens[:, :S], return_kv=True, **kw)
    cache = init_decode_cache(cfg, B, CAPACITY)
    cache = write_prefill_kv(cfg, cache, out["kvs"],
                             jnp.full((B,), S, jnp.int32))
    for i in range(4):
        pos = S + i
        ref = forward_full(params, cfg, tokens[:, :pos + 1], **kw)
        step = decode_step(params, cfg, tokens[:, pos:pos + 1],
                           jnp.full((B,), pos, jnp.int32), cache,
                           window_len=CAPACITY)
        cache = step["cache"]
        np.testing.assert_allclose(
            np.asarray(step["logits"].astype(jnp.float32)),
            np.asarray(ref["logits"][:, -1].astype(jnp.float32)),
            rtol=0.08, atol=0.08)


# ---------------------------------------------------------------------------
# the fused decode horizon == the single steps it fuses
# ---------------------------------------------------------------------------

# The fused scan and the single-step program fuse their ops differently;
# with excess precision allowed, XLA keeps a fused bf16 intermediate in
# f32 in one program and rounds it in the other. Rounded where the
# program says, the two agree bit for bit.
_EXACT = {"compiler_options": {"xla_allow_excess_precision": False}}


def _logit_bits(logits):
    """A position-weighted sum of every logit's f32 bit pattern, per row
    (uint32, wrapping): rows that differ in any bit of any logit differ
    here but for a vanishing chance of cancellation."""
    bits = jax.lax.bitcast_convert_type(logits.astype(jnp.float32),
                                        jnp.uint32)
    weights = jnp.arange(1, logits.shape[-1] + 1, dtype=jnp.uint32)
    return jnp.sum(bits * weights, axis=-1)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_fused_decode_matches_steps(arch):
    """``multi_decode_step`` at horizon 2 with a greedy sampler equals two
    ``decode_step`` calls: the same tokens, the same logits to the last
    bit, the same cache."""
    cfg, params, tokens, kw = _setup(arch)
    cache = _prefilled_cache(cfg, params, tokens, kw)
    # attention-free archs carry no pool; the engine still hands the
    # step a table, which they never read
    cache.setdefault("block_tables", jnp.zeros((B, 1), jnp.int32))
    tok0 = tokens[:, S]
    pos0 = jnp.full((B,), S, jnp.int32)

    def greedy(_key, logits):
        logits = logits.at[:, cfg.vocab_size:].set(-jnp.inf)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            _logit_bits(logits)

    fused = jax.jit(lambda p, c: multi_decode_step(
        p, cfg, tok0, pos0, jnp.full((B,), 2, jnp.int32), c,
        window_len=CAPACITY, horizon=2,
        rng_keys=jnp.zeros((2, 2), jnp.uint32), sample_fn=greedy,
        eos_id=-1, step_id=-1), **_EXACT)(params, dict(cache))
    one = jax.jit(lambda p, t, q, c: decode_step(
        p, cfg, t[:, None], q, c, window_len=CAPACITY), **_EXACT)

    c, t, q = dict(cache), tok0, pos0
    toks, bits = [], []
    for _ in range(2):
        out = one(params, t, q, c)
        c = out["cache"]
        t, b = greedy(None, out["logits"])
        toks.append(t)
        bits.append(b)
        q = q + 1
    np.testing.assert_array_equal(np.asarray(fused["tokens"]),
                                  np.stack(toks, axis=1))
    np.testing.assert_array_equal(np.asarray(fused["confidences"]),
                                  np.stack(bits, axis=1))
    assert np.asarray(fused["token_valid"]).all()
    np.testing.assert_array_equal(np.asarray(fused["positions"]),
                                  np.asarray(q))
    assert set(fused["cache"]) == set(c) - {"block_tables"}
    for key, val in fused["cache"].items():
        np.testing.assert_array_equal(np.asarray(val), np.asarray(c[key]),
                                      err_msg=key)


# ---------------------------------------------------------------------------
# chunked attention == naive attention at the switch boundary
# ---------------------------------------------------------------------------

def test_chunked_mha_matches_naive():
    B_, H, S_, hd = 2, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B_, H, S_, hd)) for kk in ks)
    from repro.kernels.ref import mha_ref
    got = L.chunked_mha(q * hd ** -0.5, k, v, chunk=64)
    want = mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_mha_window():
    B_, H, S_, hd = 1, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B_, H, S_, hd)) for kk in ks)
    from repro.kernels.ref import mha_ref
    got = L.chunked_mha(q * hd ** -0.5, k, v, chunk=64, window=100)
    want = mha_ref(q, k, v, window=100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-236b"])
def test_long_forward_uses_chunked_path(arch):
    """S > threshold forward (chunked) == short-stitched reference by
    running the same weights at S=128 naive vs chunked_mha directly."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 1536), 0,
                              cfg.vocab_size)
    out = forward_full(params, cfg, toks)  # S=1536 > 1024 -> chunked
    assert np.all(np.isfinite(np.asarray(out["logits"], np.float32)))


def test_remat_forward_matches():
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    a = forward_full(params, cfg, toks, remat=False)["logits"]
    b = forward_full(params, cfg, toks, remat=True)["logits"]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-5, atol=1e-5)
