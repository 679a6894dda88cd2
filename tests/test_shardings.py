"""Sharding-rule unit tests (no multi-device backend needed: the rules
are pure functions over shapes and an AbstractMesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import SHAPES, kv_cache_specs
from repro.configs.registry import ASSIGNED_ARCHS, get_config
from repro.launch import shardings as shd
from repro.models.init import init_params


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
    for leaf, spec in zip(leaves, specs):
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert leaf.shape[dim] % n == 0, \
                f"{leaf.shape} dim {dim} not divisible by {n} ({spec})"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_divisible(arch):
    cfg, shapes = _shapes(arch)
    for mesh in (mesh1(), mesh2()):
        specs = shd.partition_params(cfg, mesh, shapes, fsdp=True)
        _check_divisible(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-236b",
                                  "mamba2-2.7b", "granite-20b"])
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    for mesh in (mesh1(), mesh2()):
        for shape_name in ("decode_32k", "long_500k"):
            if not cfg.supports_shape(SHAPES[shape_name]):
                continue
            shapes = kv_cache_specs(cfg, shape_name)
            specs = shd.partition_cache(cfg, mesh, shape_name)
            assert set(shapes) == set(specs)
            _check_divisible(shapes, specs, mesh)


def test_expert_parallel_when_divisible():
    """deepseek (160 experts) shards E over model; mixtral (8) cannot."""
    cfg, shapes = _shapes("deepseek-v2-236b")
    specs = shd.partition_params(cfg, mesh1(), shapes, fsdp=True)
    wg = specs["layers"]["moe"]["experts"]["w_gate"]
    assert wg[1] == "model", wg  # [L, E, D, F] -> E over model

    cfg, shapes = _shapes("mixtral-8x7b")
    specs = shd.partition_params(cfg, mesh1(), shapes, fsdp=True)
    wg = specs["layers"]["moe"]["experts"]["w_gate"]
    assert wg[-1] == "model", wg  # tensor parallel on F instead


def test_megatron_pairing_dense():
    """Up-projections column-parallel, down-projections row-parallel."""
    cfg, shapes = _shapes("qwen3-1.7b")
    specs = shd.partition_params(cfg, mesh1(), shapes, fsdp=False)
    lyr = specs["layers"]
    assert lyr["attn"]["wq"][-1] == "model"
    assert lyr["attn"]["wo"][-2] == "model"
    assert lyr["mlp"]["w_gate"][-1] == "model"
    assert lyr["mlp"]["w_down"][-2] == "model"


def test_serving_fsdp_threshold():
    """Small model: no FSDP for serving; deepseek: FSDP forced."""
    cfg, shapes = _shapes("qwen3-1.7b")
    specs = shd.partition_params(cfg, mesh1(), shapes)  # auto
    # some large 2D leaf should have exactly one sharded dim (model only)
    wq = specs["layers"]["attn"]["wq"]
    assert sum(x is not None for x in wq) == 1

    cfg, shapes = _shapes("deepseek-v2-236b")
    specs = shd.partition_params(cfg, mesh1(), shapes)  # auto -> fsdp
    wg = specs["layers"]["moe"]["experts"]["w_gate"]
    assert sum(x is not None for x in wg) >= 2


def test_input_specs_batch_sharding():
    cfg = get_config("qwen3-1.7b")
    specs = shd.partition_inputs(cfg, mesh2(), "train_4k")
    assert specs["tokens"] == P(("pod", "data"), None)
    # long_500k batch=1: replicate
    specs = shd.partition_inputs(cfg, mesh2(), "long_500k")
    assert specs["tokens"] == P(None, None)


def test_kv_partition_specs_fallbacks():
    m = mesh1()
    # KVH=1 cannot shard heads -> SEQUENCE-sharded cache (flash-decoding
    # layout; sharding head_dim would force per-step cache all-gathers,
    # see EXPERIMENTS.md #Perf target 2)
    cfg = get_config("granite-20b")
    sp = shd.kv_partition_specs(cfg, m, batch=128)
    assert sp["kv"] == P(("data",), "model", None, None)
    cfg = get_config("seamless-m4t-large-v2")  # KVH=16 -> heads
    sp = shd.kv_partition_specs(cfg, m, batch=128)
    assert sp["kv"] == P(("data",), None, "model", None)
    cfg = get_config("mamba2-2.7b")     # 80 heads % 16 == 0
    sp = shd.kv_partition_specs(cfg, m, batch=128)
    assert sp["ssm"] == P(("data",), "model", None, None)
    cfg = get_config("deepseek-v2-236b")  # MLA latent -> sequence-sharded
    sp = shd.kv_partition_specs(cfg, m, batch=128)
    assert sp["mla"] == P(("data",), "model", None)
