"""Token sampling (temperature / top-k / top-p) + confidence extraction.

``sample_logits`` is the scan-compatible core: a plain traceable function
(no ``jax.jit`` wrapper, no device sync) so the fused multi-token decode
horizon can call it inside a ``lax.scan`` body once per iteration.
``sample_tokens`` is the jitted convenience wrapper the host-side code
paths (prefill first-token sampling) keep using; both produce bit-identical
samples for the same key.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.8
    top_k: int = 20
    top_p: float = 0.95
    max_new_tokens: int = 160


SELECT_CHUNK = 128   # vocabulary columns per chunk of ``top_values``


def top_values(x: jax.Array, k: int) -> jax.Array:
    """The ``k`` largest values of each row of ``x`` [B, V], descending,
    exactly, with no sort over V.

    The row splits into chunks of ``SELECT_CHUNK`` columns (the last one
    padded with -inf). Each of the ``k`` chunks with the largest maxima
    holds a value at least as large as any value in an unchosen chunk,
    so those ``k`` chunks hold the row's ``k`` largest values, ties
    included: the selection runs over the ``k`` chunk maxima's
    ``k * SELECT_CHUNK`` columns. A row of fewer than ``k`` chunks
    selects over the whole row. (A ``lax.top_k`` of the whole row
    compiles back into a full sort on a TPU once its input feeds other
    ops too, as a sampler's does.)
    """
    B, V = x.shape
    n = -(-V // SELECT_CHUNK)
    if n < k:
        return jax.lax.top_k(x, k)[0]
    x = jnp.pad(x, ((0, 0), (0, n * SELECT_CHUNK - V)),
                constant_values=-jnp.inf)
    chunks = x.reshape(B, n, SELECT_CHUNK)
    _, best = jax.lax.top_k(jnp.max(chunks, axis=-1), k)
    cand = jnp.take_along_axis(chunks, best[:, :, None], axis=1)
    return jax.lax.top_k(cand.reshape(B, k * SELECT_CHUNK), k)[0]


def sampler_path(sp: SamplingParams, vocab: int, lanewise: bool) -> str:
    """Which filter a sampler over ``vocab`` columns runs: "select"
    (``top_values``), "sort" (a full sort of each row: the lane-wise
    sampler, or top-p alone) or "none" (greedy, or no filter)."""
    if lanewise:
        return "sort"
    if sp.temperature <= 0.0:
        return "none"
    if 0 < sp.top_k < vocab:
        return "select"
    return "sort" if 0.0 < sp.top_p < 1.0 else "none"


def filter_logits(scaled: jax.Array, *, top_k: int, top_p: float):
    """``scaled`` [B, V] with what top-k and top-p drop set to -inf.

    Top-k keeps ``scaled >= kth``, the ``top_k``-th largest value (ties
    included); top-p then keeps the values down to the first whose
    cumulative softmax mass, in descending order, reaches ``top_p``.
    With top-k on, both thresholds are among the ``top_k`` largest
    values, so ``top_values`` finds them with no sort over V; top-p
    alone sorts the row. ``top_k`` / ``top_p`` are Python scalars.
    """
    if 0 < top_k < scaled.shape[-1]:
        vals = top_values(scaled, top_k)
        scaled = jnp.where(scaled < vals[:, -1:], -jnp.inf, scaled)
        if 0.0 < top_p < 1.0:
            # the descending row's softmax cumsum, over its first top_k
            # entries only: past them it holds ties of the k-th value or
            # -inf, so a cutoff index past top_k - 1 keeps what the k-th
            # value keeps
            top = vals[:, :1]
            z = jnp.sum(jnp.exp(scaled - top), axis=-1, keepdims=True)
            cum = jnp.cumsum(jnp.exp(vals - top) / z, axis=-1)
            cutoff_idx = jnp.minimum(jnp.sum(cum < top_p, axis=-1),
                                     top_k - 1)
            cutoff = jnp.take_along_axis(vals, cutoff_idx[:, None], axis=1)
            scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    elif 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=1)
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return scaled


def sample_logits(rng: jax.Array, logits: jax.Array, *,
                  temperature: float = 0.8, top_k: int = 20,
                  top_p: float = 0.95):
    """logits [B, V] -> (tokens [B], confidence [B]); scan-compatible.

    Confidence = probability the model assigned to the sampled token under
    the UNtempered distribution (the DeepConf-style signal).
    ``temperature`` / ``top_k`` / ``top_p`` must be Python scalars (they
    select the lowered graph, not traced values).

    ``temperature <= 0`` is exact greedy: a deterministic argmax that
    ignores the key entirely. (Scaling logits by 1/eps and sampling
    would break exact logit ties by the per-call gumbel noise, making
    "greedy" outputs depend on how many keys the caller consumed — e.g.
    on the decode horizon.)

    Otherwise the tempered row goes through ``filter_logits`` and the
    draw is one categorical over the whole filtered row, so a kept set
    and a key decide the token.
    """
    logits_f = logits.astype(jnp.float32)
    base_logp = jax.nn.log_softmax(logits_f, axis=-1)

    if temperature <= 0.0:
        tokens = jnp.argmax(logits_f, axis=-1)
        conf = jnp.exp(jnp.take_along_axis(base_logp, tokens[:, None],
                                           axis=1))[:, 0]
        return tokens.astype(jnp.int32), conf

    scaled = logits_f / jnp.maximum(temperature, 1e-6)
    scaled = filter_logits(scaled, top_k=top_k, top_p=top_p)
    tokens = jax.random.categorical(rng, scaled, axis=-1)
    conf = jnp.exp(jnp.take_along_axis(base_logp, tokens[:, None],
                                       axis=1))[:, 0]
    return tokens.astype(jnp.int32), conf


@partial(jax.jit, static_argnames=("temperature", "top_k", "top_p"))
def sample_tokens(rng: jax.Array, logits: jax.Array, *,
                  temperature: float = 0.8, top_k: int = 20,
                  top_p: float = 0.95):
    """Jitted wrapper over ``sample_logits`` (host-side call sites)."""
    return sample_logits(rng, logits, temperature=temperature,
                         top_k=top_k, top_p=top_p)


def sample_logits_lanes(rng: jax.Array, logits: jax.Array,
                        temperature: jax.Array, top_k: jax.Array,
                        top_p: jax.Array):
    """Lane-wise ``sample_logits``: per-row sampling params as TRACED
    [B] arrays (the per-request ``SamplingParams`` override path — one
    jit instance serves every parameter mix).

    Each lane keeps the set the scalar path keeps for its values, up to
    the rounding of the top-p sum: the scalar path finds the top-k and
    top-p thresholds with ``top_values``, this one with one full sort of
    every row (the traced ``top_k`` bounds no width), and the two sum
    the softmax denominator in another order. With the same kept set a
    lane draws the scalar call's token for the same key. Greedy lanes
    (``temperature <= 0``) are an exact argmax that ignores the key,
    matching the scalar contract; disabled filters (``top_k <= 0`` or
    ``>= V``, ``top_p`` outside (0, 1)) pass logits through unmasked.
    """
    B, V = logits.shape
    logits_f = logits.astype(jnp.float32)
    base_logp = jax.nn.log_softmax(logits_f, axis=-1)
    temperature = temperature.astype(jnp.float32)[:, None]
    top_p = top_p.astype(jnp.float32)[:, None]

    scaled = logits_f / jnp.maximum(temperature, 1e-6)

    # top-k: k-th largest value per lane via an ascending sort (the
    # scalar path's sort[:, -k]); lanes with the filter disabled keep
    # their logits (cutoff -inf)
    sorted_asc = jnp.sort(scaled, axis=-1)
    k_idx = jnp.clip(V - top_k, 0, V - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_asc, k_idx[:, None], axis=1)
    k_on = ((top_k > 0) & (top_k < V))[:, None]
    kth = jnp.where(k_on, kth, -jnp.inf)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)

    # top-p: smallest prefix of the descending-sorted distribution with
    # cumulative mass >= top_p (same cumsum-cutoff as the scalar path)
    sorted_desc = sorted_asc[:, ::-1]
    sorted_desc = jnp.where(sorted_desc < kth, -jnp.inf, sorted_desc)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p, axis=-1).astype(jnp.int32)
    cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None], axis=1)
    p_on = (top_p > 0.0) & (top_p < 1.0)
    cutoff = jnp.where(p_on, cutoff, -jnp.inf)
    scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)

    sampled = jax.random.categorical(rng, scaled, axis=-1)
    greedy = jnp.argmax(logits_f, axis=-1)
    tokens = jnp.where(temperature[:, 0] <= 0.0, greedy, sampled)
    conf = jnp.exp(jnp.take_along_axis(base_logp, tokens[:, None],
                                       axis=1))[:, 0]
    return tokens.astype(jnp.int32), conf


@jax.jit
def sample_tokens_lanes(rng: jax.Array, logits: jax.Array,
                        temperature: jax.Array, top_k: jax.Array,
                        top_p: jax.Array):
    """Jitted wrapper over ``sample_logits_lanes``."""
    return sample_logits_lanes(rng, logits, temperature, top_k, top_p)
