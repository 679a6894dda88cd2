"""Sharded serving: the Engine over a (data, model) device mesh.

Pins the tentpole equivalence: a 4-device ``(data=2, model=2)`` mesh run
of ``serve_batch`` is token-identical AND step-score-identical to the
single-device engine under a fixed RNG — including COW forks, chunked
prefill, ``decode_horizon>1``, tight-pool pruning, and multi-request
batches. The engine-level tests need 4 devices and run under the
``test-multidevice`` CI lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``); the mesh
factory and sharding-rule tests are pure and run everywhere.

Exactness rests on two properties the engine arranges (see
docs/ENGINE.md "Sharded serving"):

  * ``serving_param_specs``: only column-parallel weights shard over
    "model", so no contraction ever crosses a shard boundary — every
    collective is an all-gather, never a float reduction;
  * partitionable threefry (JAX's default), whose random bits are
    invariant to how the sampled-over array is sharded.
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import serving_config
from repro.core.pruning import make_policy
from repro.core.scorer import init_scorer
from repro.data.tokenizer import get_tokenizer
from repro.launch import shardings as shd
from repro.launch.mesh import make_host_mesh, resolve_host_mesh_shape
from repro.models.init import init_params
from repro.serving import Engine, EngineConfig, Request, SamplingParams

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")

MAX_NEW = 24
BATCH = 8


# ---------------------------------------------------------------------------
# mesh factory (pure / single-device)
# ---------------------------------------------------------------------------

def test_resolve_host_mesh_shape_adapts():
    assert resolve_host_mesh_shape(device_count=4) == (4, 1)
    assert resolve_host_mesh_shape(2, None, device_count=4) == (2, 2)
    assert resolve_host_mesh_shape(None, 2, device_count=4) == (2, 2)
    assert resolve_host_mesh_shape(1, 1, device_count=1) == (1, 1)
    assert resolve_host_mesh_shape(device_count=1) == (1, 1)


def test_resolve_host_mesh_shape_validates():
    with pytest.raises(ValueError, match="does not divide"):
        resolve_host_mesh_shape(3, None, device_count=4)
    with pytest.raises(ValueError, match="device"):
        resolve_host_mesh_shape(2, 4, device_count=4)
    with pytest.raises(ValueError, match=">= 1"):
        resolve_host_mesh_shape(0, 2, device_count=4)


def test_make_host_mesh_matches_device_count():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] * mesh.shape["model"] == jax.device_count()


@pytest.mark.parametrize("local_kvh", [1, 2])
def test_sharded_decode_kernel_equals_unsharded_on_wide_table(local_kvh):
    """``kops.paged_attention_sharded`` is bit-identical to the unsharded
    kernel over a block table wider than one decode step, with each
    shard holding ``local_kvh`` KV heads: on a (2, 2) mesh where four
    devices exist, else on (1, 1)."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    model = 2 if jax.device_count() >= 4 else 1
    mesh = make_host_mesh(model, model)
    KVH, G, hd, page, bp = model * local_kvh, 4, 32, 8, 20
    lens = jnp.array([0, 1, 127, 128, 129, 160, 77, 150], jnp.int32)
    B, NB = lens.shape[0], lens.shape[0] * bp + 2
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, KVH * G, hd))
    k_pool = jax.random.normal(ks[1], (NB, page, KVH, hd))
    v_pool = jax.random.normal(ks[2], (NB, page, KVH, hd))
    bt = jax.random.permutation(ks[3], NB)[:B * bp] \
        .reshape(B, bp).astype(jnp.int32)
    args = (q, k_pool, v_pool, bt, lens)
    mesh_out = kops.paged_attention_sharded(mesh, *args, scale=0.2)
    one_out = kops.paged_attention(*args, scale=0.2)
    np.testing.assert_array_equal(np.asarray(mesh_out), np.asarray(one_out))


# ---------------------------------------------------------------------------
# serving sharding rules (AbstractMesh / single-device)
# ---------------------------------------------------------------------------

def _param_shapes(cfg):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def test_serving_param_specs_exactness_layout():
    """Column-parallel weights shard over model; everything touched by a
    contraction or a norm reduction stays replicated."""
    cfg = serving_config()
    mesh = AbstractMesh((2, 2), ("data", "model"))
    specs = shd.serving_param_specs(cfg, mesh, _param_shapes(cfg))
    lyr = specs["layers"]
    assert lyr["attn"]["wq"][-1] == "model"
    assert lyr["mlp"]["w_gate"][-1] == "model"
    # row-parallel set replicated: a sharded contraction would psum
    assert all(e is None for e in lyr["attn"]["wo"])
    assert all(e is None for e in lyr["mlp"]["w_down"])
    # stacked per-layer norm scales [L, D] must NOT fall into the
    # generic 2-D shard-last-dim rule (a D-sharded norm weight makes
    # every following QKV/MLP contraction a partial-sum)
    assert all(e is None for e in lyr["ln1"])
    assert all(e is None for e in lyr["ln2"])
    assert all(e is None for e in specs["final_norm"])


def test_serving_cache_specs_paged_pool_layout():
    cfg = serving_config()  # num_kv_heads=2: divides model=2
    mesh = AbstractMesh((2, 2), ("data", "model"))
    specs = shd.serving_cache_specs(cfg, mesh)
    assert specs["k_pool"] == P(None, None, None, "model", None)
    assert specs["v_pool"] == P(None, None, None, "model", None)
    # heads that don't divide the model axis: replicate, never shard hd
    mesh16 = AbstractMesh((2, 16), ("data", "model"))
    specs = shd.serving_cache_specs(cfg, mesh16)
    assert specs["k_pool"] == P(None, None, None, None, None)


def test_serving_step_shardings_cover_cache():
    cfg = serving_config()
    mesh = make_host_mesh()  # whatever this session has
    ss = shd.serving_step_shardings(cfg, mesh)
    assert set(ss["pools"]) == {"k_pool", "v_pool"}
    assert set(ss["layer_pool"]) == {"k_pool", "v_pool"}
    for key in ("lane", "table", "hidden", "act", "prefill_act",
                "replicated"):
        assert key in ss


# ---------------------------------------------------------------------------
# engine over a mesh (4 simulated devices)
# ---------------------------------------------------------------------------

_STATE: dict = {}


def _setup():
    if "cfg" not in _STATE:
        cfg = serving_config()
        _STATE["cfg"] = cfg
        _STATE["params"] = init_params(cfg, jax.random.PRNGKey(0))
        _STATE["scorer"] = init_scorer(jax.random.PRNGKey(1), cfg.d_model)
        tok = get_tokenizer()
        _STATE["tok"] = tok
        _STATE["prompts"] = [tok.encode(p, add_bos=True)
                             for p in ("3+5-2=", "7*2+1=", "9-4+6=")]
    return (_STATE["cfg"], _STATE["params"], _STATE["scorer"],
            _STATE["tok"], _STATE["prompts"])


def _ecfg(K=1, temperature=0.8, num_blocks=64, chunk=None,
          max_new=MAX_NEW):
    return EngineConfig(
        max_batch=BATCH, num_blocks=num_blocks, capacity=128,
        max_new_tokens=max_new,
        sampling=SamplingParams(temperature=temperature,
                                top_k=0 if temperature == 0.0 else 20,
                                top_p=1.0 if temperature == 0.0 else 0.95,
                                max_new_tokens=max_new),
        prefill_chunk_size=chunk,
        decode_horizon=K)


def _engine_pair(key):
    """(single-device, mesh) engines compiled once per config, reused
    across property examples (the per-example reset is the RNG key)."""
    cfg, params, scorer, _, _ = _setup()
    pairs = _STATE.setdefault("pairs", {})
    if key not in pairs:
        K, temp, blocks, chunk, mesh_shape = key
        ecfg = _ecfg(K, temp, blocks, chunk)
        single = Engine(params, cfg, ecfg, make_policy("step"),
                        scorer_params=scorer)
        mesh = make_host_mesh(*mesh_shape)
        sharded = Engine(params, cfg, ecfg, make_policy("step"),
                         scorer_params=scorer, mesh=mesh)
        pairs[key] = (single, sharded)
    return pairs[key]


def _serve(eng, requests, rng_seed):
    eng._rng = jax.random.PRNGKey(rng_seed)
    results = eng.serve_batch(
        [Request(request_id=r.request_id,
                 prompt_tokens=list(r.prompt_tokens),
                 n_traces=r.n_traces, policy=make_policy("step"))
         for r in requests])
    assert eng.pool_drained()
    eng.block_mgr.check_invariants()
    return results


def _assert_identical(res_a, res_b):
    for a, b in zip(res_a, res_b):
        assert [t.output_tokens for t in a.traces] \
            == [t.output_tokens for t in b.traces]
        # scores are float32 sigmoids of bit-identical hidden states:
        # exact equality is the claim, not a tolerance
        assert [t.step_scores for t in a.traces] \
            == [t.step_scores for t in b.traces]
        assert [t.token_confidences for t in a.traces] \
            == [t.token_confidences for t in b.traces]
        assert [t.status for t in a.traces] == [t.status for t in b.traces]
        assert a.num_pruned == b.num_pruned
        assert a.answer == b.answer


@needs4
@settings(max_examples=6, deadline=None)
@given(st.sampled_from((1, 4)), st.sampled_from((None, 8)),
       st.integers(0, 2), st.integers(2, 6), st.booleans(),
       st.integers(0, 10 ** 6))
def test_mesh_token_identical(K, chunk, prompt_idx, n_traces, greedy,
                              rng_seed):
    """(data=2, model=2) serve_batch == single-device serve_batch:
    same tokens, same step scores, same confidences, same statuses —
    across decode horizons, chunked prefill, and sampling modes (the
    shared-prefix default means every example exercises COW forks)."""
    _, _, _, _, prompts = _setup()
    temp = 0.0 if greedy else 0.8
    single, sharded = _engine_pair((K, temp, 64, chunk, (2, 2)))
    reqs = [Request(request_id=0, prompt_tokens=prompts[prompt_idx],
                    n_traces=n_traces)]
    _assert_identical(_serve(single, reqs, rng_seed),
                      _serve(sharded, reqs, rng_seed))


@needs4
@pytest.mark.parametrize("mesh_shape", [(4, 1), (1, 4)])
def test_mesh_axis_extremes(mesh_shape):
    """Pure data-parallel (4,1) and pure tensor-parallel (1,4) meshes
    are also token-identical (kv heads don't divide model=4: the pool
    replicates, params still shard where divisible)."""
    _, _, _, _, prompts = _setup()
    single, sharded = _engine_pair((1, 0.8, 64, None, mesh_shape))
    reqs = [Request(request_id=0, prompt_tokens=prompts[0], n_traces=4)]
    _assert_identical(_serve(single, reqs, 123),
                      _serve(sharded, reqs, 123))


@needs4
def test_mesh_tight_pool_pruning_identical():
    """Memory pressure: COW forks + STEP pruning decisions land on the
    same traces at the same ticks on the mesh."""
    _, _, _, _, prompts = _setup()
    single, sharded = _engine_pair((1, 0.8, 12, None, (2, 2)))
    reqs = [Request(request_id=0, prompt_tokens=prompts[1], n_traces=6)]
    res_a = _serve(single, reqs, 77)
    res_b = _serve(sharded, reqs, 77)
    _assert_identical(res_a, res_b)


@needs4
def test_mesh_chunked_prefill_identical():
    """Chunked prompt prefill (reservation take/commit, paged chunk
    attention) composes with the mesh."""
    _, _, _, tok, _ = _setup()
    long_prompt = tok.encode("1+2-3+4-5+6-7+8=", add_bos=True)
    single, sharded = _engine_pair((1, 0.8, 64, 8, (2, 2)))
    reqs = [Request(request_id=0, prompt_tokens=long_prompt, n_traces=3)]
    _assert_identical(_serve(single, reqs, 5), _serve(sharded, reqs, 5))


@needs4
def test_mesh_multi_request_horizon_identical():
    """Cross-request contention + fused decode horizon on the mesh."""
    _, _, _, _, prompts = _setup()
    single, sharded = _engine_pair((4, 0.8, 64, None, (2, 2)))
    reqs = [Request(request_id=0, prompt_tokens=prompts[0], n_traces=3),
            Request(request_id=1, prompt_tokens=prompts[2], n_traces=3)]
    _assert_identical(_serve(single, reqs, 42), _serve(sharded, reqs, 42))


@needs4
def test_mesh_rejects_indivisible_batch():
    cfg, params, scorer, _, _ = _setup()
    mesh = make_host_mesh(4, 1)
    ecfg = dataclasses.replace(_ecfg(), max_batch=6)  # 6 % 4 != 0
    with pytest.raises(ValueError, match="max_batch"):
        Engine(params, cfg, ecfg, make_policy("step"),
               scorer_params=scorer, mesh=mesh)


@needs4
def test_mesh_rejects_uncovered_archs():
    """The bit-identity contract is enforced, not assumed: archs whose
    reductions the exactness layout doesn't constrain are refused."""
    cfg, params, scorer, _, _ = _setup()
    mesh = make_host_mesh(2, 2)
    # pin a float pool: under REPRO_KV_DTYPE=int8 (the kv-quant CI
    # lane) resolve_kv_dtype rejects these archs first with its own
    # NotImplementedError — this test asserts the MESH rejection
    # message; the quantized gating has its own pin in test_kv_quant.py
    ecfg = dataclasses.replace(_ecfg(), kv_dtype="bf16")
    ssm_cfg = dataclasses.replace(cfg, arch_type="ssm")
    with pytest.raises(NotImplementedError, match="paged-attention"):
        Engine(params, ssm_cfg, ecfg, make_policy("step"),
               scorer_params=scorer, mesh=mesh)
    mla_cfg = dataclasses.replace(cfg, use_mla=True)
    with pytest.raises(NotImplementedError, match="MLA/MoE"):
        Engine(params, mla_cfg, ecfg, make_policy("step"),
               scorer_params=scorer, mesh=mesh)


@needs4
def test_mesh_use_kernel_validated_at_construction():
    """Engine(mesh=..., use_kernel=True) is never silently unvalidated:
    layouts the shard_map kernel path doesn't cover raise a clear
    NotImplementedError at construction, and "auto" falls back to the
    dense path instead."""
    cfg, params, scorer, _, _ = _setup()
    # (1, 4): num_kv_heads=2 doesn't divide model=4 -> uncovered
    mesh = make_host_mesh(1, 4)
    with pytest.raises(NotImplementedError, match="shard_map"):
        Engine(params, cfg, dataclasses.replace(_ecfg(), use_kernel=True),
               make_policy("step"), scorer_params=scorer, mesh=mesh)
    eng = Engine(params, cfg,
                 dataclasses.replace(_ecfg(), use_kernel="auto"),
                 make_policy("step"), scorer_params=scorer, mesh=mesh)
    assert eng.use_kernel is False  # auto: dense fallback, same tokens


@needs4
def test_mesh_use_kernel_token_identical():
    """The covered layout (heads divide "model") routes the paged
    kernels through shard_map: lanes on "data", pool KV heads computed
    shard-locally on "model". Grid cells are independent, so the mesh
    kernel engine is token- and score-identical to the single-device
    kernel engine."""
    cfg, params, scorer, _, prompts = _setup()
    ecfg = dataclasses.replace(_ecfg(K=2, max_new=16), use_kernel=True)
    single = Engine(params, cfg, ecfg, make_policy("step"),
                    scorer_params=scorer)
    sharded = Engine(params, cfg, ecfg, make_policy("step"),
                     scorer_params=scorer, mesh=make_host_mesh(2, 2))
    assert sharded.use_kernel is True
    reqs = [Request(request_id=0, prompt_tokens=prompts[0], n_traces=4)]
    _assert_identical(_serve(single, reqs, 99), _serve(sharded, reqs, 99))


@needs4
def test_mesh_use_kernel_chunked_prefill_identical():
    """Chunked prefill through the multi-query kernel (batch-1 chunk
    jobs: "model"-sharded heads, data-replicated tiles) composes with
    the mesh and stays identical to the single-device kernel engine."""
    cfg, params, scorer, tok, _ = _setup()
    long_prompt = tok.encode("1+2-3+4-5+6-7+8=", add_bos=True)
    ecfg = dataclasses.replace(_ecfg(chunk=8, max_new=16),
                               use_kernel=True)
    single = Engine(params, cfg, ecfg, make_policy("step"),
                    scorer_params=scorer)
    sharded = Engine(params, cfg, ecfg, make_policy("step"),
                     scorer_params=scorer, mesh=make_host_mesh(2, 2))
    reqs = [Request(request_id=0, prompt_tokens=long_prompt, n_traces=3)]
    _assert_identical(_serve(single, reqs, 5), _serve(sharded, reqs, 5))


@needs4
def test_mesh_params_actually_sharded():
    """The mesh engine's params really live distributed: a wq shard on
    one device holds 1/model of the columns."""
    _, _, _, _, prompts = _setup()
    _, sharded = _engine_pair((1, 0.0, 64, None, (2, 2)))
    wq = sharded.params["layers"]["attn"]["wq"]
    assert len(wq.sharding.device_set) == 4
    shard = wq.addressable_shards[0]
    assert shard.data.shape[-1] == wq.shape[-1] // 2  # model=2
    np.testing.assert_array_equal(
        np.asarray(shard.data, np.float32),
        np.asarray(wq[..., :wq.shape[-1] // 2], np.float32))
