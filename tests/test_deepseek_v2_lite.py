"""DeepSeek-V2-Lite's mechanisms against plain references, at CPU size.

- The engine (one-shot prefill, chunked prefill from the prefix cache,
  decode through the latent pool; dense and Pallas-interpret paths)
  against the benchmark family's plain f32 reference
  (``bench/families/deepseek_v2/reference.py``, loaded by path), on the
  log-probability of every served token: the logits at the tokens the
  engine chose.
- The expert shares: four chips' routed parts, plus the shared experts
  once, sum to the uncut layer.
- The dropless layer's output per token does not depend on the batch
  shape it arrives in.
- YaRN against a direct transcription of the published formula.
- The latent kernel against the dense latent decode; the expert FFN
  kernel against the XLA ragged matmuls.
"""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.deepseek_v2_lite import full_config, smoke_config
from repro.configs.registry import get_config
from repro.core.pruning import make_policy
from repro.data.tokenizer import get_tokenizer
from repro.kernels import paged_attention as pa
from repro.models import layers as L
from repro.models.init import init_params
from repro.serving import Engine, EngineConfig, Request, SamplingParams

ROOT = Path(__file__).resolve().parents[1]
FAMILY = ROOT / "bench" / "families" / "deepseek_v2"

# a tiny DeepSeek-V2: one dense layer, then two MoE layers of which this
# chip holds 2 of the router's 8 experts; YaRN over a 128-token original
# context, so positions past it take the interpolated frequencies
TINY = dataclasses.replace(
    smoke_config(), num_layers=3, num_experts=8, experts_held=2,
    num_experts_per_tok=2, rope_original_max_positions=128)
MODEL = {f.name: getattr(TINY, f.name) for f in dataclasses.fields(TINY)
         if isinstance(getattr(TINY, f.name), (int, float, bool))}


def _load(part):
    spec = importlib.util.spec_from_file_location(
        f"deepseek_v2_{part}", FAMILY / f"{part}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    weights, reference = _load("weights"), _load("reference")
    tok = get_tokenizer()
    params, scorer = jax.jit(weights.make_weights, static_argnums=(0, 2, 3))(
        tuple(sorted(MODEL.items())), jax.random.PRNGKey(5), tok.step_id,
        2.0)
    return params, scorer, reference, tok


def _reference_logprobs(reference, params, scorer, seq):
    """log p(seq[i + 1] | seq[: i + 1]) for every i, from the reference."""
    T = -(-len(seq) // reference.BLOCK) * reference.BLOCK
    toks = np.zeros((T,), np.int32)
    toks[: len(seq)] = seq
    tgt = np.roll(toks, -1)[None]
    out = reference.forward(params, scorer, jnp.asarray(toks),
                            jnp.asarray(tgt), m=tuple(sorted(MODEL.items())))
    return (np.asarray(out["tgt"][0], np.float64)
            - np.asarray(out["lse"], np.float64))[: len(seq) - 1]


@pytest.mark.parametrize("use_kernel", [False, True])
def test_engine_matches_reference(model, use_kernel):
    """Two requests through one engine with the prefix cache on: the first
    prompt (under a chunk) prefills one-shot and parks its full blocks;
    the second shares its first block, which it takes from the cache, and
    chunk-prefills the rest, three chunks; both decode 40 tokens through
    the latent pool. Every served token's log-probability (its
    confidence, under the untempered distribution) is the reference's.

    Tolerances: bf16 weights and activations against f32 at the highest
    precision, with an untied head whose logits spread by about one; a
    token whose top-2 experts are a near tie in the router can take the
    other expert in bf16 and move by a few tenths (one-shot forward
    against the reference over 512 random tokens at this size: median
    0.010, largest 0.32). Here the served tokens read median 0.011 and
    0.012, largest 0.06 and 0.16 (dense, kernel), so the median is held
    to 0.03 and every error to 0.6. Without the YaRN softmax factor the
    median reads 0.42, with the top-k weights renormalised 0.06, with
    plain rope 0.67."""
    params, scorer, reference, tok = model
    ecfg = EngineConfig(
        max_batch=4, num_blocks=64, capacity=256, max_new_tokens=40,
        sampling=SamplingParams(temperature=0.7, top_k=0, top_p=1.0,
                                max_new_tokens=40),
        prefill_chunk_size=32, prefix_cache=True, use_kernel=use_kernel,
        kv_dtype="bf16", faults=None, seed=3)
    eng = Engine(params, TINY, ecfg, make_policy("sc"),
                 scorer_params=scorer)
    assert eng.prefix_cache is not None and eng._chunk_supported
    rng = np.random.default_rng(0)
    first = rng.integers(32, MODEL["vocab_size"], 24).tolist()
    second = first[:16] + rng.integers(32, MODEL["vocab_size"], 90).tolist()
    errs = []
    for i, prompt in enumerate((first, second)):
        res = eng.serve_batch([Request(request_id=i, prompt_tokens=prompt,
                                       n_traces=2)])[0]
        if i == 1:
            assert res.metrics.cached_tokens == 16
        for trace in res.traces:
            out = list(trace.output_tokens)
            ref = _reference_logprobs(reference, params, scorer,
                                      prompt + out)[len(prompt) - 1:]
            got = np.log(np.asarray(trace.token_confidences, np.float64))
            assert len(got) == len(out) > 0
            errs += list(np.abs(got - ref))
    assert eng.pool_drained()
    # the MoE counters: every decode token routes top-2 in two MoE
    # layers; the held experts (2 of 8) take some of the pairs; the
    # busiest held expert at least its share of them
    c = eng.counters
    assert c.moe_pairs_routed == c.decode_tokens * 2 * 2 > 0
    assert 0 < c.moe_pairs_held < c.moe_pairs_routed
    assert 2 * c.moe_load_max >= c.moe_pairs_held
    errs = np.asarray(errs)
    assert np.median(errs) < 0.03, (np.median(errs), errs.max())
    assert errs.max() < 0.6, errs.max()


def test_expert_shares_sum_to_uncut_layer():
    """Four chips of a 64-expert layer, 16 experts each: every share's
    routed part (its output less the shared experts), summed, plus the
    shared experts once, is the uncut layer. The uncut layer holds all
    64; each share routes over all 64 and computes only its own."""
    cfg = dataclasses.replace(smoke_config(), num_experts=64,
                              num_experts_per_tok=6, experts_held=0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    f32 = jnp.float32
    whole, _, load = L.moe_dropless(p, cfg, x)
    assert int(jnp.sum(load)) == 2 * 24 * 6
    shared = L.swiglu(p["shared"], x.reshape(-1, cfg.d_model)).reshape(
        x.shape).astype(f32)
    share_cfg = dataclasses.replace(cfg, experts_held=16)
    total = shared
    held = 0
    for s in range(4):
        ps = {**p, "experts": {k: v[16 * s:16 * (s + 1)]
                               for k, v in p["experts"].items()}}
        out, _, load = L.moe_dropless(ps, share_cfg, x, first_expert=16 * s)
        total = total + (out.astype(f32) - shared)
        held += int(jnp.sum(load))
    assert held == 2 * 24 * 6
    # bf16 outputs rounded once per share and once whole: a few ulps of
    # values near 1 (bf16 ulp 2^-7)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole, f32),
                               atol=4e-2, rtol=2e-2)


def test_dropless_moe_batch_shape_invariance():
    """No pair is dropped, so a token's output and its load do not
    depend on which tokens share its call: the same 128 tokens as
    [2, 64] and as [1, 128] give the same bits (Mixtral: renormalised
    top-2 of 4 experts, no shared experts)."""
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.d_model)
                          ).astype(jnp.bfloat16)
    a, _, la = L.moe_dropless(p, cfg, x)
    b, _, lb = L.moe_dropless(p, cfg, x.reshape(1, 128, cfg.d_model))
    assert int(jnp.sum(la)) == 128 * cfg.num_experts_per_tok
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    np.testing.assert_array_equal(
        np.asarray(a, np.float32).reshape(-1, cfg.d_model),
        np.asarray(b, np.float32).reshape(-1, cfg.d_model))


def _published_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding (modeling_deepseek.py), in numpy
    float32 as the published torch code computes it."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ar = np.arange(0, dim, 2, dtype=np.float32)
    freq_extra = 1.0 / (base ** (ar / dim))
    freq_inter = 1.0 / (factor * base ** (ar / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def test_yarn_matches_published_formula():
    cfg = full_config()
    want = _published_yarn(64, 10000.0, 40.0, 4096, 32, 1)
    np.testing.assert_allclose(L.yarn_inv_freq(cfg, 64), want, rtol=1e-6)
    # the blend: original frequencies at the fast end, interpolated ones
    # at the slow end
    assert want[0] == 1.0 and np.isclose(want[-1] * 40, 1e4 ** (-62 / 64))
    mscale = 1 + 0.1 * 0.707 * math.log(40)
    assert abs(L.yarn_mscale(40.0, 0.707) - mscale) < 1e-12
    assert abs(mscale - 1.2608) < 1e-4
    assert L.mla_softmax_scale(cfg) == pytest.approx(
        mscale ** 2 / math.sqrt(192), rel=1e-12)
    pos = jnp.asarray([0, 1, 4095, 50000])
    cos, sin = L.rope_cos_sin(pos, 64, 1e4, yarn=cfg)
    ang = np.asarray(pos, np.float64)[:, None] * want[None].astype(np.float64)
    # mscale == mscale_all_dim, so cos/sin are unscaled
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-3)


def test_latent_kernel_matches_dense():
    """The latent face (interpret mode) against the dense latent decode:
    an empty lane (only its own entry), a partial last page, a lane of
    many pages over several DMA steps, and a lane whose window rolled."""
    H, E, Lr, page, bp, nb, la = 4, 128, 64, 16, 24, 100, 3
    window = bp * page
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(ks[0], (la, nb, page, E)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[1], (4, H, E)).astype(jnp.bfloat16)
    own = jax.random.normal(ks[2], (4, E)).astype(jnp.bfloat16)
    bt = jax.random.permutation(ks[3], nb)[: 4 * bp].reshape(4, bp)
    pos = jnp.asarray([0, 37, 300, window + 21], jnp.int32)
    layer = jnp.asarray(2, jnp.int32)
    dense = L.latent_attention(q, pool, layer, bt, pos, own, scale=0.125,
                               window_len=window, out_dim=Lr)
    kern = pa.paged_latent_attention(q, pool, layer, bt, pos, own,
                                     scale=0.125, window=window, out_dim=Lr,
                                     pages_per_step=4, interpret=True)
    # f32 accumulation on both sides; the kernel's bf16 probabilities in
    # the value dot and bf16 outputs: a few bf16 ulps
    np.testing.assert_allclose(np.asarray(kern, np.float32),
                               np.asarray(dense, np.float32),
                               atol=2e-2, rtol=2e-2)
    # the empty lane attends to its own entry alone
    np.testing.assert_allclose(
        np.asarray(kern[0], np.float32),
        np.broadcast_to(np.asarray(own[0, :Lr], np.float32), (H, Lr)),
        atol=1e-2)


def test_expert_ffn_kernel_matches_xla():
    """The dropless layer through the Pallas expert FFN (interpret mode)
    and through XLA ragged matmuls, on a share of the experts with some
    held experts chosen by no token."""
    cfg = dataclasses.replace(smoke_config(), num_experts=16,
                              num_experts_per_tok=3, experts_held=8)
    params = init_params(cfg, jax.random.PRNGKey(2))
    p = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 10, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    a, _, la = L.moe_dropless(p, cfg, x, use_kernel=False)
    b, _, lb = L.moe_dropless(p, cfg, x, use_kernel=True, tile=8)
    assert (la == lb).all()
    # the same bf16 roundings, f32 sums in another order
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=2e-2, rtol=2e-2)
