"""The scalar sampler's top-k/top-p filter against the two-sort sampler it
replaced: the same kept set and the same token for the same key, over
ties at the k-th value, -inf vocabulary padding, vocabularies that are
not a multiple of the selection's chunk and ones too small to chunk.

``ref_sample_logits`` is the plain reference: the former body of
``sample_logits``, which sorted the whole row once for the k-th value
and once more for the top-p cumulative sum.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.sampling import (SamplingParams, filter_logits,
                                    sample_logits, sample_logits_lanes,
                                    sampler_path, top_values)

TEMPERATURE = 0.6
B = 8
KEYS = 6


def ref_filter(scaled, top_k, top_p):
    """(filtered row, the top-p cumulative sum or None) as the two-sort
    sampler computed them."""
    cum = None
    if top_k > 0 and top_k < scaled.shape[-1]:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=1)
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return scaled, cum


def ref_sample_logits(rng, logits, *, temperature=0.8, top_k=20,
                      top_p=0.95):
    logits_f = logits.astype(jnp.float32)
    base_logp = jax.nn.log_softmax(logits_f, axis=-1)

    if temperature <= 0.0:
        tokens = jnp.argmax(logits_f, axis=-1)
        conf = jnp.exp(jnp.take_along_axis(base_logp, tokens[:, None],
                                           axis=1))[:, 0]
        return tokens.astype(jnp.int32), conf

    scaled = logits_f / jnp.maximum(temperature, 1e-6)
    scaled, _ = ref_filter(scaled, top_k, top_p)

    tokens = jax.random.categorical(rng, scaled, axis=-1)
    conf = jnp.exp(jnp.take_along_axis(base_logp, tokens[:, None],
                                       axis=1))[:, 0]
    return tokens.astype(jnp.int32), conf


def make_logits(kind, seed):
    """[B, V] logits of one kind (see ``KINDS``)."""
    rng = np.random.default_rng(seed)
    if kind == "f32":            # V a multiple of the chunk
        x = rng.normal(0.0, 3.0, (B, 4096)).astype(np.float32)
    elif kind == "bf16":         # bf16-rounded, V not a multiple
        x = np.asarray(jnp.asarray(rng.normal(0.0, 3.0, (B, 5000)),
                                   jnp.bfloat16).astype(jnp.float32))
    elif kind == "ties":         # a few levels: many ties at the k-th
        x = rng.integers(0, 41, (B, 3000)).astype(np.float32) / 4.0
    elif kind == "kth_ties":     # a few leaders, then 40 ties past top_k
        x = rng.normal(-4.0, 1.0, (B, 4096)).astype(np.float32)
        for row in x:
            cols = rng.permutation(4096)[:50]
            row[cols[:10]] = 2.0 + rng.uniform(0.0, 1.0, 10)
            row[cols[10:]] = 1.5
    elif kind == "pad":          # -inf past V, as mask_and_gather writes
        x = rng.normal(0.0, 3.0, (B, 4224)).astype(np.float32)
        x[:, 4100:] = -np.inf
    else:                        # "small": fewer chunks than top_k
        x = rng.normal(0.0, 3.0, (B, 512)).astype(np.float32)
    return jnp.asarray(x, jnp.bfloat16 if kind in ("bf16", "ties")
                       else jnp.float32)


KINDS = ("f32", "bf16", "ties", "kth_ties", "pad", "small")


@pytest.mark.parametrize("top_p", [0.5, 0.95, 1.0])
@pytest.mark.parametrize("top_k", [1, 20])
@pytest.mark.parametrize("kind", KINDS)
def test_same_kept_set_and_token_as_the_two_sorts(kind, top_k, top_p):
    logits = make_logits(kind, seed=1000 * KINDS.index(kind) + 10 * top_k
                         + int(100 * top_p))
    scaled = logits.astype(jnp.float32) / TEMPERATURE
    got = jax.jit(partial(filter_logits, top_k=top_k, top_p=top_p))(scaled)
    want, cum = jax.jit(partial(ref_filter, top_k=top_k,
                                top_p=top_p))(scaled)
    # only a row whose reference sum lies within 1e-6 of top_p may differ
    near = np.zeros((B,), bool) if cum is None else np.any(
        np.abs(np.asarray(cum) - top_p) < 1e-6, axis=-1)
    kept = np.isfinite(np.asarray(got))
    same = np.all(np.asarray(got) == np.asarray(want), axis=-1)
    assert np.all(same | near), np.flatnonzero(~same)

    kw = dict(temperature=TEMPERATURE, top_k=top_k, top_p=top_p)
    new = jax.jit(partial(sample_logits, **kw))
    ref = jax.jit(partial(ref_sample_logits, **kw))
    for i in range(KEYS):
        key = jax.random.PRNGKey(i)
        tok, conf = map(np.asarray, new(key, logits))
        tok_ref, conf_ref = map(np.asarray, ref(key, logits))
        assert np.all((tok == tok_ref) | near)
        np.testing.assert_array_equal(conf[same], conf_ref[same])
        assert np.all(kept[np.arange(B), tok])

@pytest.mark.parametrize("kind", KINDS)
def test_lanes_draw_the_scalar_token(kind):
    """The lane-wise sampler (one full sort) with every lane at the
    scalar call's values keeps the same set and draws the same token."""
    logits = make_logits(kind, seed=77)
    kw = dict(temperature=TEMPERATURE, top_k=20, top_p=0.95)
    lanes = [jnp.full((B,), v, dt) for v, dt in (
        (TEMPERATURE, jnp.float32), (20, jnp.int32), (0.95, jnp.float32))]
    _, cum = ref_filter(logits.astype(jnp.float32) / TEMPERATURE, 20, 0.95)
    near = np.any(np.abs(np.asarray(cum) - 0.95) < 1e-6, axis=-1)
    for i in range(KEYS):
        key = jax.random.PRNGKey(200 + i)
        tok = np.asarray(jax.jit(partial(sample_logits, **kw))(key,
                                                               logits)[0])
        tok_l = np.asarray(jax.jit(sample_logits_lanes)(key, logits,
                                                        *lanes)[0])
        assert np.all((tok == tok_l) | near)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", [1, 20])
def test_top_values_are_the_sorted_row_head(kind, k):
    x = make_logits(kind, seed=11).astype(jnp.float32)
    want = jnp.sort(x, axis=-1)[:, ::-1][:, :k]
    np.testing.assert_array_equal(np.asarray(top_values(x, k)),
                                  np.asarray(want))


@pytest.mark.parametrize("temperature, top_k, top_p", [
    (0.0, 20, 0.95),       # greedy
    (0.6, 0, 0.95),        # top-p alone
    (0.6, 0, 1.0),         # no filter
    (0.6, 4096, 0.95),     # top_k >= V: top-p alone
], ids=["greedy", "top_p_only", "unfiltered", "top_k_ge_vocab"])
def test_paths_without_selection_are_unchanged(temperature, top_k, top_p):
    logits = make_logits("f32", seed=5)
    kw = dict(temperature=temperature, top_k=top_k, top_p=top_p)
    for i in range(KEYS):
        key = jax.random.PRNGKey(100 + i)
        tok, conf = jax.jit(partial(sample_logits, **kw))(key, logits)
        tok_ref, conf_ref = jax.jit(partial(ref_sample_logits, **kw))(
            key, logits)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_ref))
        np.testing.assert_array_equal(np.asarray(conf),
                                      np.asarray(conf_ref))


@pytest.mark.parametrize("sp, lanewise, path", [
    (SamplingParams(0.6, 20, 0.95), False, "select"),
    (SamplingParams(0.6, 20, 1.0), False, "select"),
    (SamplingParams(0.6, 0, 0.95), False, "sort"),
    (SamplingParams(0.6, 4096, 0.95), False, "sort"),
    (SamplingParams(0.6, 0, 1.0), False, "none"),
    (SamplingParams(0.0, 20, 0.95), False, "none"),
    (SamplingParams(0.6, 20, 0.95), True, "sort"),
    (SamplingParams(0.0, 0, 1.0), True, "sort"),
])
def test_sampler_path(sp, lanewise, path):
    assert sampler_path(sp, 4096, lanewise) == path

