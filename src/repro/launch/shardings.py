"""Sharding rules for the serving engine over a ("data", "model") mesh
(docs/ENGINE.md "Sharded serving"): parameters, the paged decode cache,
prefill KV and the jitted steps' lane/activation layouts.

The layout trades a third of the tensor-parallel memory win for
EXACTNESS: column-parallel weights shard over "model" as usual, but the
row-parallel contraction set (wo / w_down / w_out) is replicated, so
GSPMD's only cross-shard collectives are all-gathers of activations —
pure data movement, never a floating-point reduction. A bf16 psum from
a row-parallel contraction rounds partial sums differently than the
single-device matmul and flips near-tie samples; with this layout the
sharded engine's logits are bit-identical to the single-device
engine's, which is what lets CI pin token-identity across device
counts.

Everything is expressed as PartitionSpec trees over jax.eval_shape
pytrees — nothing here touches real devices.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# column-parallel weights: the "model" axis shards their output dim
_MODEL_LAST = {"wq", "wk", "wv", "w_gate", "w_up", "wq_a", "wq_b", "wkv_a",
               "wk_b", "wv_b", "router", "w_in", "lm_head", "conv_w"}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
    return ""


def to_named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _div(n: int, axis_n: int) -> bool:
    return n >= axis_n and n % axis_n == 0


def serving_param_specs(cfg: ModelConfig, mesh, shapes_tree):
    """PartitionSpec tree for serving params (exactness-preserving TP).

    Only the column-parallel matmul set (``_MODEL_LAST``, on its OUTPUT
    dim) and the embedding's vocab dim shard over "model", and only
    when that exact dim divides the axis. EVERYTHING else is
    replicated: the row-parallel contraction weights (a sharded
    contraction psums), the stacked per-layer norm scales ``[L, D]``
    (sharded on D, they would turn every downstream QKV/MLP contraction
    into a partial-sum), and there is no fallback to *other* dims: one
    could land the "model" axis on a contraction or layer-stack dim when
    the output dim doesn't divide, silently breaking the bit-identity
    contract.

    No FSDP: serving carries no optimizer state, and the decode path
    re-reads every weight each step — "data" is reserved for the
    trace batch.
    """
    model_n = mesh.shape["model"]

    def one(path, leaf):
        ndim = leaf.ndim
        if ndim == 0:
            return P()
        spec = [None] * ndim
        name = _leaf_name(path)
        if name == "embed" and _div(leaf.shape[0], model_n):
            spec[0] = "model"  # vocab dim: gather + D-contraction, exact
        elif name in _MODEL_LAST and ndim >= 2 \
                and _div(leaf.shape[-1], model_n):
            spec[-1] = "model"  # column-parallel output dim
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, shapes_tree)


def serving_cache_specs(cfg: ModelConfig, mesh,
                        kv_dtype: str = "bf16") -> dict:
    """PartitionSpecs for the engine's paged decode cache
    (``init_decode_cache`` keys; ``block_tables`` excluded — the tables
    are host-side scheduler state, uploaded data-sharded per tick).

    Paged pools ``[L*, NB, bs, KVH, hd]``: KV heads over "model" when
    they divide it (each shard holds its heads' slice of EVERY block);
    the block dim stays replicated over "data" so any lane reads any
    block locally — the host allocator stays global and per-tick writes
    move only ``[B, KVH, hd]`` activations, never cache bytes. Per-slot
    recurrent state and cross-attention caches shard their batch dim
    over "data" with the lanes that own them. MLA's fused latent pool
    is replicated (the latent dim is contracted by every head).

    Quantized pools (``kv_dtype`` int8/fp8) add per-slot scale arrays
    ``k_scale``/``v_scale`` ``[L*, NB, bs, KVH]`` sharded exactly like
    the pools they describe: KV heads over "model", blocks replicated.

    The MLA/ssm/hybrid/enc-dec branches record the INTENDED layout for
    archs ``Engine._place_on_mesh`` still refuses (NotImplementedError)
    — unreachable from the engine today, kept so lifting the guard is a
    constraint-audit, not a design task.
    """
    model_n = mesh.shape["model"]
    out: dict = {}
    if cfg.attention_layer_ids():
        if cfg.use_mla:
            out["kv_pool"] = P(None, None, None, None)
        else:
            kvh = "model" if _div(cfg.num_kv_heads, model_n) else None
            out["k_pool"] = P(None, None, None, kvh, None)
            out["v_pool"] = P(None, None, None, kvh, None)
            from repro.models.kv_quant import is_quantized
            if is_quantized(kv_dtype):
                out["k_scale"] = P(None, None, None, kvh)
                out["v_scale"] = P(None, None, None, kvh)
    if cfg.arch_type in ("ssm", "hybrid"):
        out["ssm_state"] = P(None, "data", None, None, None)
        out["conv_state"] = P(None, "data", None, None)
    if cfg.is_encoder_decoder:
        kvh = "model" if _div(cfg.num_kv_heads, model_n) else None
        out["cross_k"] = P(None, "data", None, kvh, None)
        out["cross_v"] = P(None, "data", None, kvh, None)
    return out


def serving_prefill_kv_specs(cfg: ModelConfig, mesh) -> dict:
    """NamedShardings for the PER-LAYER prefill KV/state tensors
    (``forward_full(return_kv=True)``'s ``kv_specs`` hook) on the
    serving mesh. Prefill runs per request at batch 1, so only head
    dims shard; keeping the emitted KV head-aligned with the pool
    specs means the pool scatter never reshards cache bytes."""
    model_n = mesh.shape["model"]
    out = {}
    if cfg.num_kv_heads and cfg.head_dim:
        kvh = "model" if _div(cfg.num_kv_heads, model_n) else None
        out["kv"] = P(None, None, kvh, None)
    if cfg.use_mla:
        out["mla"] = P(None, None, None)
    if cfg.arch_type in ("ssm", "hybrid"):
        out["ssm"] = P(None, None, None, None)
        out["conv"] = P(None, None, None)
    return {k: NamedSharding(mesh, s) for k, s in out.items()}


def serving_step_shardings(cfg: ModelConfig, mesh,
                           kv_dtype: str = "bf16") -> dict:
    """The NamedSharding bundle the engine threads through its jitted
    steps (``Engine._build_steps``) and into
    ``multi_decode_step(shard_specs=...)``:

      lane        [B]        trace-batch state over "data"
      table       [B, ...]   block tables / per-lane [B, K] outputs
      hidden      [B, D]     last hidden state — data-sharded, so the
                             step scorer is a shard-local matmul
                             (score capture without cross-device
                             gathers)
      act         [B, 1, *]  decode attention/MLP outputs right before
                             their row contraction (exact-TP gather
                             point, see serving_param_specs)
      prefill_act [1, S, *]  same gather point for batch-1 prefills
      pools       stacked-cache dict (serving_cache_specs)
      layer_pool  per-layer pool slices inside the layer scan
      replicated  RNG keys, scorer params, batch-1 prefill logits
    """
    cache = serving_cache_specs(cfg, mesh, kv_dtype)
    return {
        "lane": NamedSharding(mesh, P("data")),
        "table": NamedSharding(mesh, P("data", None)),
        "hidden": NamedSharding(mesh, P("data", None)),
        "act": NamedSharding(mesh, P("data", None, None)),
        "prefill_act": NamedSharding(mesh, P(None, None, None)),
        "pools": {k: NamedSharding(mesh, s) for k, s in cache.items()},
        "layer_pool": {k: NamedSharding(mesh, P(*s[1:]))
                       for k, s in cache.items()},
        "replicated": NamedSharding(mesh, P()),
    }
