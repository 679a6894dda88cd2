"""Serving sharding-rule unit tests at published widths (no multi-device
backend needed: the rules are pure functions over shapes and an
AbstractMesh)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import ASSIGNED_ARCHS, get_config
from repro.launch import shardings as shd
from repro.models.init import init_params
from repro.models.model import init_decode_cache

_ROW_PARALLEL = {"wo", "w_down", "w_out"}


def mesh1():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh2():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _shapes(arch):
    cfg = get_config(arch)
    return cfg, jax.eval_shape(lambda: init_params(cfg,
                                                   jax.random.PRNGKey(0)))


def _check_divisible(shapes_tree, spec_tree, mesh):
    leaves, _ = jax.tree_util.tree_flatten(shapes_tree)
    specs, _ = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(specs)
    for leaf, spec in zip(leaves, specs):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert leaf.shape[dim] % n == 0, \
                f"{leaf.shape} dim {dim} not divisible by {n} ({spec})"


def _named_specs(spec_tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    return [(shd._leaf_name(path), spec) for path, spec in flat]


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_serving_param_specs_contract(arch):
    """The exactness contract of ``serving_param_specs``: only the
    column-parallel set (on its output dim) and the embedding's vocab
    dim shard, every sharded dim divides its axis, and the row-parallel
    weights and the stacked norm scales stay replicated."""
    cfg, shapes = _shapes(arch)
    for mesh in (mesh1(), mesh2()):
        specs = shd.serving_param_specs(cfg, mesh, shapes)
        _check_divisible(shapes, specs, mesh)
        sharded = 0
        for name, spec in _named_specs(specs):
            if name in _ROW_PARALLEL or "norm" in name \
                    or name.startswith("ln"):
                assert all(e is None for e in spec), (name, spec)
            dims = [d for d, e in enumerate(spec) if e is not None]
            if not dims:
                continue
            sharded += 1
            assert [spec[d] for d in dims] == ["model"], (name, spec)
            if name == "embed":
                assert dims == [0], spec
            else:
                assert name in shd._MODEL_LAST, (name, spec)
                assert dims == [len(spec) - 1], (name, spec)
        assert sharded, f"{arch}: nothing sharded over model"


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-236b",
                                  "mamba2-2.7b", "granite-20b"])
def test_serving_cache_specs_divisible(arch):
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: init_decode_cache(cfg, 128, 4096, num_blocks=1024))
    shapes.pop("block_tables", None)
    for mesh in (mesh1(), mesh2()):
        specs = shd.serving_cache_specs(cfg, mesh)
        assert set(shapes) == set(specs)
        for key in specs:
            assert len(specs[key]) == len(shapes[key].shape), key
        _check_divisible(shapes, specs, mesh)


def test_serving_pairing_dense():
    """Up-projections column-parallel; down-projections replicated (the
    mesh's exact layout, where a training layout would make them
    row-parallel)."""
    cfg, shapes = _shapes("qwen3-1.7b")
    lyr = shd.serving_param_specs(cfg, mesh1(), shapes)["layers"]
    for name in ("wq", "wk", "wv"):
        assert lyr["attn"][name] == P(None, None, "model"), name
    assert lyr["mlp"]["w_gate"] == P(None, None, "model")
    assert lyr["mlp"]["w_up"] == P(None, None, "model")
    assert all(e is None for e in lyr["attn"]["wo"])
    assert all(e is None for e in lyr["mlp"]["w_down"])


def test_serving_cache_specs_heads_fallback():
    m = mesh1()
    # KVH=1 cannot shard its heads over model=16: the pool replicates
    # them (never the head_dim, never the block dim)
    cfg = get_config("granite-20b")
    sp = shd.serving_cache_specs(cfg, m)
    assert sp["k_pool"] == P(None, None, None, None, None)
    assert sp["v_pool"] == P(None, None, None, None, None)
    # KVH=16 divides: heads over model, cross caches alike, their batch
    # over data
    cfg = get_config("seamless-m4t-large-v2")
    sp = shd.serving_cache_specs(cfg, m)
    assert sp["k_pool"] == P(None, None, None, "model", None)
    assert sp["cross_k"] == P(None, "data", None, "model", None)
    # recurrent state: per-slot batch over data
    cfg = get_config("mamba2-2.7b")
    sp = shd.serving_cache_specs(cfg, m)
    assert sp["ssm_state"] == P(None, "data", None, None, None)
    assert sp["conv_state"] == P(None, "data", None, None)


def test_serving_step_shardings_batch_on_data():
    cfg = get_config("qwen3-1.7b")
    ss = shd.serving_step_shardings(cfg, mesh1())
    assert ss["lane"].spec == P("data")
    assert ss["table"].spec == P("data", None)
    assert ss["hidden"].spec == P("data", None)
    assert ss["act"].spec == P("data", None, None)
    assert ss["replicated"].spec == P()
