"""The Pallas kernel path as the engine's production attention path.

Pins the tentpole contract: engine output — tokens, step scores, token
confidences, prune decisions, statuses — is IDENTICAL with
``use_kernel=True`` (multi-query paged kernels, interpret mode on CPU)
vs ``False`` (dense jnp fallbacks) under a fixed RNG, across the
decode-horizon, chunked-prefill and tight-pool (memory-pressure pruning)
configurations. Both paths follow the same numerics contract (f32
accumulation, zeros for empty rows), so the only residual difference is
online-vs-flat softmax reduction order — which the bf16 activation casts
absorb at serving scale.

Also covers ``use_kernel="auto"`` resolution (kernel on TPU, dense on
CPU, dense fallback on uncovered meshes; MLA takes the latent face) and
the ``REPRO_USE_KERNEL``
env override the CI kernel lane uses.

Under a quantized pool (``REPRO_KV_DTYPE=int8``/``fp8`` — the CI
kv-quant lane) the identity contract narrows to what the paper's
pruning decisions actually consume: tokens, prune counts, statuses and
the answer stay EXACTLY equal, while step scores / token confidences
are held to a tight drift bound instead of bitwise equality. The
decode face stays bit-identical even quantized (bf16-grid scales keep
``code * scale`` exact in f32), but the chunked-prefill face's
online-softmax rescale is only bitwise-equal to the dense one-shot
softmax when the pooled prefix holds the row max — quantization noise
can flip near-ties, surfacing reduction-order ulps in confidences.
"""
import dataclasses
import types

import jax
import pytest

from repro.configs.registry import get_config, serving_config
from repro.core.pruning import make_policy
from repro.core.scorer import init_scorer
from repro.data.tokenizer import get_tokenizer
from repro.models.init import init_params
from repro.models import kv_quant
from repro.serving import (Engine, EngineConfig, Request, SamplingParams,
                           resolve_use_kernel)
from repro.serving.engine import _default_use_kernel

MAX_NEW = 24

# CI's kv-quant lane re-runs this file under REPRO_KV_DTYPE=int8
_QUANTIZED = kv_quant.is_quantized(EngineConfig().kv_dtype)
_DRIFT = 1e-3


@pytest.fixture(scope="module")
def setup():
    cfg = serving_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scorer = init_scorer(jax.random.PRNGKey(1), cfg.d_model)
    tok = get_tokenizer()
    return cfg, params, scorer, tok


def _ecfg(use_kernel, K=1, chunk=None, num_blocks=64, temperature=0.8,
          max_new=MAX_NEW):
    return EngineConfig(
        max_batch=8, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new,
        sampling=SamplingParams(
            temperature=temperature,
            top_k=0 if temperature == 0.0 else 20,
            top_p=1.0 if temperature == 0.0 else 0.95,
            max_new_tokens=max_new),
        prefill_chunk_size=chunk, decode_horizon=K,
        use_kernel=use_kernel)


def _serve(setup, use_kernel, prompt_text, n_traces, seed, **ecfg_kw):
    cfg, params, scorer, tok = setup
    eng = Engine(params, cfg, _ecfg(use_kernel, **ecfg_kw),
                 make_policy("step"), scorer_params=scorer)
    eng._rng = jax.random.PRNGKey(seed)
    res = eng.serve(tok.encode(prompt_text, add_bos=True), n_traces)
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
    return res


def _close(xs, ys):
    return len(xs) == len(ys) and all(
        len(x) == len(y) and all(abs(u - v) <= _DRIFT for u, v in zip(x, y))
        for x, y in zip(xs, ys))


def _assert_identical(a, b):
    assert [t.output_tokens for t in a.traces] \
        == [t.output_tokens for t in b.traces]
    sa = [t.step_scores for t in a.traces]
    sb = [t.step_scores for t in b.traces]
    ca = [t.token_confidences for t in a.traces]
    cb = [t.token_confidences for t in b.traces]
    if _QUANTIZED:  # bounded drift, see module docstring
        assert _close(sa, sb)
        assert _close(ca, cb)
    else:
        assert sa == sb
        assert ca == cb
    assert [t.status for t in a.traces] == [t.status for t in b.traces]
    assert a.num_pruned == b.num_pruned
    assert a.answer == b.answer


@pytest.mark.parametrize("K,chunk,blocks,temperature", [
    (1, None, 64, 0.0),    # greedy baseline
    (4, None, 64, 0.8),    # fused decode horizon
    (1, 4, 64, 0.8),       # chunked prefill (prompt > chunk)
    (1, None, 12, 0.8),    # tight pool: memory-pressure pruning
    (4, 4, 12, 0.8),       # all three at once
])
def test_engine_kernel_vs_dense_identical(setup, K, chunk, blocks,
                                          temperature):
    kw = dict(K=K, chunk=chunk, num_blocks=blocks, temperature=temperature)
    res_d = _serve(setup, False, "3+5-2=", 6, seed=7, **kw)
    res_k = _serve(setup, True, "3+5-2=", 6, seed=7, **kw)
    _assert_identical(res_d, res_k)


def test_engine_kernel_vs_dense_multi_request(setup):
    cfg, params, scorer, tok = setup
    results = {}
    for uk in (False, True):
        eng = Engine(params, cfg, _ecfg(uk, K=2), make_policy("step"),
                     scorer_params=scorer)
        eng._rng = jax.random.PRNGKey(42)
        results[uk] = eng.serve_batch([
            Request(request_id=0,
                    prompt_tokens=tok.encode("7*2+1=", add_bos=True),
                    n_traces=3, policy=make_policy("step")),
            Request(request_id=1,
                    prompt_tokens=tok.encode("9-4+6=", add_bos=True),
                    n_traces=3, policy=make_policy("step")),
        ])
    for a, b in zip(results[False], results[True]):
        _assert_identical(a, b)


# ---------------------------------------------------------------------------
# use_kernel resolution
# ---------------------------------------------------------------------------

def test_resolve_use_kernel_auto_cpu_is_dense():
    """On a CPU host "auto" keeps the dense XLA path (the kernels would
    only run in slow interpret mode); explicit True forces interpret."""
    cfg = serving_config()
    assert jax.default_backend() == "cpu"
    assert resolve_use_kernel("auto", cfg) is False
    assert resolve_use_kernel(True, cfg) is True
    assert resolve_use_kernel(False, cfg) is False


def test_resolve_use_kernel_rejects_mla():
    """MLA is rejected only on a mesh, which has no latent routing (True
    raises, "auto" falls back); on one device it resolves to the latent
    kernel face like GQA resolves to the paged kernel: True forces it
    (interpret mode here), "auto" keeps the dense path on a CPU host (and
    takes the kernel on a TPU)."""
    cfg = get_config("deepseek-v2-lite", smoke=True)
    assert resolve_use_kernel(True, cfg) is True
    assert resolve_use_kernel("auto", cfg) is False
    assert resolve_use_kernel(False, cfg) is False
    mesh = types.SimpleNamespace(shape={"data": 1, "model": 1})
    with pytest.raises(NotImplementedError, match="latent"):
        resolve_use_kernel(True, cfg, mesh)
    assert resolve_use_kernel("auto", cfg, mesh) is False


def test_resolve_use_kernel_rejects_garbage():
    with pytest.raises(ValueError, match="use_kernel"):
        resolve_use_kernel("yes please", serving_config())


def test_env_override_flips_default(monkeypatch):
    monkeypatch.setenv("REPRO_USE_KERNEL", "1")
    assert _default_use_kernel() is True
    assert EngineConfig().use_kernel is True
    monkeypatch.setenv("REPRO_USE_KERNEL", "auto")
    assert EngineConfig().use_kernel == "auto"
    monkeypatch.delenv("REPRO_USE_KERNEL")
    assert EngineConfig().use_kernel is False
