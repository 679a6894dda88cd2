"""Architecture-dispatching forward passes.

Three entry points, shared by training and serving:

  forward_full(...)  — full-sequence forward (train / prefill), scan over
                       stacked layer params.
  decode_step(...)   — one-token decode against the paged KV cache; also
                       returns the last hidden state so the STEP scorer can
                       run fused with generation.
  encode(...)        — encoder stack for enc-dec archs (stub frontend
                       embeddings in, memory out).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kv_quant
from repro.models import layers as L
from repro.models.init import padded_vocab


def _embed(params, cfg, tokens):
    return params["embed"][tokens]


def _wsc_kv(kv_specs, key, x):
    # Constrain a per-layer KV/state tensor to the serving mesh's
    # sharding (``serving_prefill_kv_specs``; no-op without a mesh).
    if kv_specs is None or key not in kv_specs or x is None:
        return x
    return jax.lax.with_sharding_constraint(x, kv_specs[key])


def _stacks(params, cfg) -> list:
    """The decoder's layer stacks in order, as (params stacked over the
    stack's layers, index of its first layer, MoE?): the leading dense
    layers (``cfg.first_k_dense``, a dense MLP of width ``d_ff``), then
    the rest."""
    out = []
    if cfg.first_k_dense:
        out.append((params["dense_layers"], 0, False))
    out.append((params["layers"], cfg.first_k_dense, cfg.uses_moe))
    return out


def _split_experts(stack):
    """A MoE stack's layer params without the routed experts, and the
    experts stacked over its layers: the scans carry the first as xs and
    hand the second whole, with a layer index, to the expert FFN, so no
    layer's expert weights are sliced out (copied) to feed a kernel."""
    moe = dict(stack["moe"])
    experts = moe.pop("experts")
    return {**stack, "moe": moe}, experts


def _logits(params, cfg, h):
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


# ---------------------------------------------------------------------------
# encoder (enc-dec archs; consumes stub frontend embeddings)
# ---------------------------------------------------------------------------

def encode(params: dict, cfg: ModelConfig, encoder_embeds: jax.Array,
           remat: bool = False, act_spec=None) -> jax.Array:
    h = encoder_embeds
    B, T, D = h.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def wsc(x):
        if act_spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, act_spec)

    def body(h, lp):
        a = L.gqa_attention_full(lp["attn"], cfg,
                                 L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                 positions, window=None)
        h = h + a
        m = L.swiglu(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return wsc(h + m), None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, wsc(h), params["encoder"])
    return L.rms_norm(h, params["encoder_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def forward_full(params: dict, cfg: ModelConfig, tokens: jax.Array,
                 modality_embeds: Optional[jax.Array] = None,
                 encoder_embeds: Optional[jax.Array] = None,
                 use_kernel: bool = False,
                 return_kv: bool = False,
                 remat: bool = False,
                 act_spec=None,
                 kv_specs=None,
                 tp_act_spec=None) -> dict:
    """Returns {logits, hidden, aux_loss[, kvs]}.

    ``tp_act_spec`` (serving mesh prefill): the sharding the
    attention/MLP activations are constrained to around their row
    contractions, so the exactness-preserving tensor-parallel layout
    never partial-sums (see ``layers.swiglu``).

    ``remat=True`` checkpoints each layer body (save only the residual
    stream per layer; recompute attention/ffn intermediates in backward).

    ``act_spec`` (sharding for [B, S, D]) pins the residual-stream
    sharding between layers (serving mesh prefill)."""
    B, S = tokens.shape

    def wsc(x):
        if act_spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, act_spec)

    h = wsc(_embed(params, cfg, tokens))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.modality == "vision" and modality_embeds is not None:
        n = modality_embeds.shape[1]
        h = jnp.concatenate(
            [modality_embeds.astype(h.dtype), h[:, n:]], axis=1)

    window = cfg.sliding_window

    if cfg.arch_type == "ssm":
        def body(h, lp):
            x_in = L.rms_norm(h, lp["norm"], cfg.norm_eps)
            if return_kv:
                out, ss, cs = L.mamba2_mixer_full(
                    lp["mixer"], cfg, x_in, use_kernel=use_kernel,
                    return_state=True)
                return wsc(h + out), (_wsc_kv(kv_specs, "ssm", ss),
                                      _wsc_kv(kv_specs, "conv", cs))
            out = L.mamba2_mixer_full(lp["mixer"], cfg, x_in,
                                      use_kernel=use_kernel)
            return wsc(h + out), None
        if remat:
            body = jax.checkpoint(body)
        h, kvs = jax.lax.scan(body, h, params["layers"])

    elif cfg.arch_type == "hybrid":
        sa = params["shared_attn"]

        def group_body(h, gp):
            def layer_body(h, lp):
                x_in = L.rms_norm(h, lp["norm"], cfg.norm_eps)
                if return_kv:
                    out, ss, cs = L.mamba2_mixer_full(
                        lp["mixer"], cfg, x_in, use_kernel=use_kernel,
                        return_state=True)
                    return h + out, (_wsc_kv(kv_specs, "ssm", ss),
                                     _wsc_kv(kv_specs, "conv", cs))
                out = L.mamba2_mixer_full(lp["mixer"], cfg, x_in,
                                          use_kernel=use_kernel)
                return h + out, None
            if remat:
                layer_body = jax.checkpoint(layer_body)
            h, states = jax.lax.scan(layer_body, h, gp)
            a_in = L.rms_norm(h, sa["ln1"], cfg.norm_eps)
            if return_kv:
                a, kv = L.gqa_attention_full(sa["attn"], cfg, a_in, positions,
                                             window=window, return_kv=True,
                                             use_kernel=use_kernel)
                kv = (_wsc_kv(kv_specs, "kv", kv[0]),
                      _wsc_kv(kv_specs, "kv", kv[1]))
            else:
                a = L.gqa_attention_full(sa["attn"], cfg, a_in, positions,
                                         window=window,
                                         use_kernel=use_kernel)
                kv = None
            h = h + a
            h = h + L.swiglu(sa["mlp"], L.rms_norm(h, sa["ln2"], cfg.norm_eps))
            return wsc(h), (states, kv) if return_kv else None

        if remat:
            group_body = jax.checkpoint(group_body)
        h, kvs = jax.lax.scan(group_body, h, params["layers"])

    else:  # dense / moe / vlm / enc-dec decoder
        enc_kv = None
        if cfg.is_encoder_decoder:
            assert encoder_embeds is not None
            enc_out = encode(params, cfg, encoder_embeds,
                             remat=remat, act_spec=act_spec)

        def body(carry, lp, is_moe=cfg.uses_moe):
            h, aux = carry
            a_in = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
            if cfg.use_mla and return_kv:
                a, kv = L.mla_attention_full(lp["attn"], cfg, a_in, positions,
                                             return_kv=True,
                                             act_spec=tp_act_spec)
                kv = _wsc_kv(kv_specs, "mla", kv)
            elif cfg.use_mla:
                a = L.mla_attention_full(lp["attn"], cfg, a_in, positions,
                                         act_spec=tp_act_spec)
                kv = None
            elif return_kv:
                a, kv = L.gqa_attention_full(lp["attn"], cfg, a_in, positions,
                                             window=window, return_kv=True,
                                             use_kernel=use_kernel,
                                             act_spec=tp_act_spec)
                kv = (_wsc_kv(kv_specs, "kv", kv[0]),
                      _wsc_kv(kv_specs, "kv", kv[1]))
            else:
                a = L.gqa_attention_full(lp["attn"], cfg, a_in, positions,
                                         window=window,
                                         use_kernel=use_kernel,
                                         act_spec=tp_act_spec)
                kv = None
            h = h + a
            if cfg.is_encoder_decoder:
                c = L.cross_attention(
                    lp["cross"], cfg,
                    L.rms_norm(h, lp["ln_cross"], cfg.norm_eps),
                    *L.cross_kv(lp["cross"], cfg, enc_out))
                h = h + c
            m_in = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            if is_moe:
                m, aux_l, _ = L.moe_dropless(lp["moe"], cfg, m_in)
                aux = aux + aux_l
            else:
                m = L.swiglu(lp["mlp"], m_in, act_spec=tp_act_spec)
            return (wsc(h + m), aux), kv

        kv_parts = []
        for stack, _, is_moe in _stacks(params, cfg):
            step = partial(body, is_moe=is_moe)
            if remat:
                step = jax.checkpoint(step)
            (h, aux_total), kvs = jax.lax.scan(step, (h, aux_total), stack)
            kv_parts.append(kvs)
        if len(kv_parts) > 1 and return_kv:
            kvs = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                               *kv_parts)

    hidden = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, hidden)
    out = {"logits": logits, "hidden": hidden, "aux_loss": aux_total}
    if return_kv:
        out["kvs"] = kvs
    return out


# ---------------------------------------------------------------------------
# one-token decode against the paged cache
# ---------------------------------------------------------------------------

def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                positions: jax.Array, cache: dict, window_len: int,
                use_kernel: bool = False, shard_specs=None) -> dict:
    """tokens [B,1]; positions [B]; cache per ``init_decode_cache``.

    window_len: static cache capacity in tokens (rolling buffer when the
    sequence outgrows it). Returns {logits [B,V], hidden [B,D], cache}.

    ``shard_specs`` (launch/shardings.serving_step_shardings) makes the
    step mesh-aware: per-layer pool updates are pinned to the serving
    cache layout and the last hidden state is constrained to the
    data-sharded lane layout, so a step scorer consuming it
    (``multi_decode_step``'s ``score_fn``) runs shard-local — no
    cross-device gather per scored token.
    """
    B = tokens.shape[0]
    h = _embed(params, cfg, tokens)  # [B,1,D]
    new_cache = dict(cache)
    layer_pool = {} if shard_specs is None else shard_specs["layer_pool"]
    act = None if shard_specs is None else shard_specs["act"]
    # mesh + kernel: route the paged-attention kernel through shard_map
    # (lanes on "data", pool KV heads on "model", computed shard-local)
    kmesh = (shard_specs["lane"].mesh
             if use_kernel and shard_specs is not None else None)

    def wsc_h(x):
        # pin the residual stream AND the norm outputs feeding the
        # column-parallel projections to the lane layout: left
        # unconstrained, GSPMD may shard them on D inside the layer
        # scan, turning the QKV/MLP contractions over D into
        # cross-shard partial sums (inexact rounding)
        if act is None:
            return x
        return jax.lax.with_sharding_constraint(x, act)

    h = wsc_h(h)

    if cfg.arch_type == "ssm":
        def body(h, xs):
            lp, sstate, cstate = xs
            out, ns, nc = L.mamba2_mixer_decode(
                lp["mixer"], cfg,
                L.rms_norm(h, lp["norm"], cfg.norm_eps), sstate, cstate)
            return h + out, (ns, nc)
        h, (ns, ncv) = jax.lax.scan(
            body, h, (params["layers"], cache["ssm_state"],
                      cache["conv_state"]))
        new_cache["ssm_state"], new_cache["conv_state"] = ns, ncv

    elif cfg.arch_type == "hybrid":
        sa = params["shared_attn"]

        def group_body(h, xs):
            gp, sstate, cstate, k_pool, v_pool = xs

            def layer_body(h, lxs):
                lp, ss, cs = lxs
                out, ns, nc = L.mamba2_mixer_decode(
                    lp["mixer"], cfg,
                    L.rms_norm(h, lp["norm"], cfg.norm_eps), ss, cs)
                return h + out, (ns, nc)
            h, (ns, ncv) = jax.lax.scan(layer_body, h, (gp, sstate, cstate))
            a_in = L.rms_norm(h, sa["ln1"], cfg.norm_eps)
            a, (nk, nv) = L.gqa_attention_decode(
                sa["attn"], cfg, a_in, positions,
                {"k_pool": k_pool, "v_pool": v_pool,
                 "block_tables": cache["block_tables"],
                 "window_len": window_len, "use_kernel": use_kernel,
                 "kernel_mesh": kmesh,
                 "pool_spec": layer_pool.get("k_pool"),
                 "act_spec": act}, 0)
            h = h + a
            h = h + L.swiglu(sa["mlp"], L.rms_norm(h, sa["ln2"], cfg.norm_eps))
            return h, (ns, ncv, nk, nv)

        # ssm_state is stacked [n_ssm, ...] = [G*per, ...]; regroup
        G = cfg.num_layers // cfg.hybrid_attn_every
        per = cfg.hybrid_attn_every
        ss = cache["ssm_state"].reshape(G, per, *cache["ssm_state"].shape[1:])
        cs = cache["conv_state"].reshape(G, per, *cache["conv_state"].shape[1:])
        h, (ns, ncv, nk, nv) = jax.lax.scan(
            group_body, h,
            (params["layers"], ss, cs, cache["k_pool"], cache["v_pool"]))
        new_cache["ssm_state"] = ns.reshape(-1, *ns.shape[2:])
        new_cache["conv_state"] = ncv.reshape(-1, *ncv.shape[2:])
        new_cache["k_pool"], new_cache["v_pool"] = nk, nv

    elif cfg.use_mla:
        def attend(p, a_in, i):
            return L.mla_attention_decode(
                p, cfg, a_in, positions, cache["kv_pool"], i,
                cache["block_tables"], window_len, use_kernel=use_kernel,
                act_spec=act)

        h, entries, load = _latent_layers(params, cfg, h, attend,
                                          use_kernel, act, wsc_h)
        slot = positions % window_len if window_len > 0 else positions
        new_cache["kv_pool"] = _write_latent(
            cfg, cache["kv_pool"], cache["block_tables"], slot, entries)

    else:  # dense / moe / vlm / enc-dec decoder
        if cfg.first_k_dense:
            raise NotImplementedError(
                "leading dense layers are served on the latent (MLA) path")
        has_cross = cfg.is_encoder_decoder
        quant = "k_scale" in cache  # quantized paged pool (int8/fp8)

        def body(h, xs):
            lp, k_pool, v_pool = xs[0], xs[1], xs[2]
            ksc, vsc = (xs[3], xs[4]) if quant else (None, None)
            cross = xs[3 + 2 * quant:] if has_cross else None
            a_in = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
            a, out_pools = L.gqa_attention_decode(
                lp["attn"], cfg, a_in, positions,
                {"k_pool": k_pool, "v_pool": v_pool,
                 "k_scale": ksc, "v_scale": vsc,
                 "block_tables": cache["block_tables"],
                 "window_len": window_len, "use_kernel": use_kernel,
                 "kernel_mesh": kmesh,
                 "pool_spec": layer_pool.get("k_pool"),
                 "scale_spec": layer_pool.get("k_scale"),
                 "act_spec": act}, 0)
            h = h + a
            if has_cross:
                ck, cv = cross
                c = L.cross_attention(
                    lp["cross"], cfg,
                    L.rms_norm(h, lp["ln_cross"], cfg.norm_eps), ck, cv)
                h = h + c
            m_in = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            if cfg.uses_moe:
                m, _, load = L.moe_dropless(lp["moe"], cfg, m_in,
                                            use_kernel=use_kernel)
                out_pools = out_pools + (load,)
            else:
                m = L.swiglu(lp["mlp"], m_in, act_spec=act)
            return wsc_h(h + m), out_pools

        xs = (params["layers"], cache["k_pool"], cache["v_pool"])
        if quant:
            xs = xs + (cache["k_scale"], cache["v_scale"])
        if has_cross:
            xs = xs + (cache["cross_k"], cache["cross_v"])
        h, out_pools = jax.lax.scan(body, h, xs)
        new_cache["k_pool"], new_cache["v_pool"] = out_pools[:2]
        if quant:
            new_cache["k_scale"], new_cache["v_scale"] = out_pools[2:4]
        if cfg.uses_moe:
            load = jnp.sum(out_pools[-1], axis=0)

    hidden = L.rms_norm(h[:, 0], params["final_norm"], cfg.norm_eps)  # [B,D]
    if shard_specs is not None:
        hidden = jax.lax.with_sharding_constraint(hidden,
                                                  shard_specs["hidden"])
    logits = _logits(params, cfg, hidden)
    out = {"logits": logits, "hidden": hidden, "cache": new_cache}
    if cfg.uses_moe:
        out["expert_load"] = load
    return out


def _latent_layers(params, cfg, h, attend, use_kernel, act=None,
                   wsc_h=None):
    """The decoder's layers over the latent pool (read only by
    ``attend(attn_params, a_in, layer) -> (a, entries)``), stack by stack.
    Returns (h, entries stacked over all layers, expert load [T, Eh]
    summed over the MoE layers, or None)."""
    entries, loads = [], []
    for stack, first, is_moe in _stacks(params, cfg):
        n = jax.tree.leaves(stack)[0].shape[0]
        experts = None
        if is_moe:
            stack, experts = _split_experts(stack)

        def body(h, xs, is_moe=is_moe, first=first, experts=experts):
            lp, i = xs
            a, entry = attend(lp["attn"], L.rms_norm(h, lp["ln1"],
                                                     cfg.norm_eps), i)
            h = h + a
            m_in = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            if is_moe:
                m, _, load = L.moe_dropless(
                    lp["moe"], cfg, m_in, experts=experts, layer=i - first,
                    use_kernel=use_kernel)
            else:
                m, load = L.swiglu(lp["mlp"], m_in, act_spec=act), None
            h = h + m
            return (h if wsc_h is None else wsc_h(h)), (entry, load)

        h, (ent, load) = jax.lax.scan(
            body, h, (stack, first + jnp.arange(n, dtype=jnp.int32)))
        entries.append(ent)
        if load is not None:
            loads.append(jnp.sum(load, axis=0))
    entries = jnp.concatenate(entries, axis=0) if len(entries) > 1 \
        else entries[0]
    return h, entries, (sum(loads) if loads else None)


def _write_latent(cfg, pool, block_tables, slot, entries, valid=None):
    """Write each layer's new latent entries into the pool in place:
    pool [La, NB, bs, E]; slot [B] or [B, C] (cache slots); entries
    [La, B, E] or [La, B, C, E]; entries not ``valid`` go to the scratch
    block 0."""
    bs = cfg.kv_block_size
    idx = slot // bs
    if idx.ndim == 1:
        idx = idx[:, None]
    bid = jnp.take_along_axis(block_tables, idx, axis=1).reshape(slot.shape)
    if valid is not None:
        bid = jnp.where(valid, bid, 0)
    entries = L.pad_last(entries, pool.shape[-1]).astype(pool.dtype)
    # one (layer, block, offset) index per entry, so each update is one
    # row of the pool's minor dimension and the pool keeps its layout
    la = entries.shape[0]
    layer = jnp.arange(la, dtype=bid.dtype).reshape((la,) + (1,) * bid.ndim)
    return pool.at[layer, bid[None], (slot % bs)[None]].set(entries)


# ---------------------------------------------------------------------------
# fused multi-token decode horizon: K decode iterations per device call
# ---------------------------------------------------------------------------

def multi_decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                      positions: jax.Array, limits: jax.Array, cache: dict,
                      *, window_len: int, horizon: int, rng_keys: jax.Array,
                      sample_fn, eos_id: int, step_id: int,
                      score_fn=None, scratch_block: int = 0,
                      use_kernel: bool = False, shard_specs=None) -> dict:
    """Run ``horizon`` decode iterations inside one ``lax.scan``.

    The host consumes tokens/confidences/step-scores once per K tokens
    instead of paying a device->host round trip per token — the decode
    horizon behind ``EngineConfig.decode_horizon``.

    Inputs (all fixed-shape over the decode batch B):
      tokens    [B]   previous sampled token per lane (the decode input)
      positions [B]   absolute write position of that token
      limits    [B]   per-lane iteration cap (<= horizon): lanes stop
                      after ``limits`` emitted tokens (remaining
                      max-new-token allowance / secured frontier blocks);
                      0 marks a dead slot that never runs
      rng_keys  [K, 2] one PRNG key per iteration, shared by all lanes —
                      the same key stream K successive single-token
                      ticks would consume, so horizon=K reproduces
                      horizon=1 token-for-token under a fixed RNG as
                      long as scheduling stays aligned (a lane
                      shortened below the full horizon by memory
                      contention falls behind the shared key stream —
                      but in that regime horizon=1 makes different
                      pruning decisions anyway; greedy sampling is
                      key-free and only subject to the scheduling-level
                      divergence)
      sample_fn (key, logits [B, Vp]) -> (tokens [B], conf [B]); applies
                      vocab masking + temperature/top-k/top-p
      score_fn  optional (hidden [B, D]) -> [B] step scorer, evaluated
                      every iteration and validity-masked to step
                      boundaries (input token == ``step_id``)

    Lane lifecycle inside the scan: a lane is *active* until it emits
    ``eos_id`` or exhausts its limit. Inactive lanes keep decoding (the
    batch shape is fixed) but their block-table row is repointed at
    ``scratch_block`` (the allocator's dead-slot block) so their KV
    writes land in scratch, their positions freeze, and their outputs
    are validity-masked — exactly the host scheduler's dead-slot
    convention.

    Returns {tokens [B, K], confidences [B, K], scores [B, K],
    token_valid [B, K], score_valid [B, K], final_tokens [B],
    positions [B], cache} where ``token_valid`` marks a contiguous
    emitted prefix per lane and ``score_valid`` the step-boundary subset.
    ``cache`` excludes ``block_tables`` (the in-scan copy is scratch-
    masked and not meaningful to the caller). A MoE model adds
    ``expert_load`` [Eh]: the running lanes' routed pairs that fell on
    each held expert, summed over the MoE layers and the iterations.

    ``shard_specs`` (launch/shardings.serving_step_shardings) runs the
    scan over a device mesh: the scan carry (pools, per-lane state,
    block tables) is constrained to the serving layout every iteration
    so the carry sharding is a stable fixpoint, and the per-iteration
    step scorer consumes the data-sharded hidden state locally.
    """
    B = tokens.shape[0]
    active0 = limits > 0
    bt0 = jnp.where(active0[:, None], cache["block_tables"], scratch_block)
    pools = {k: v for k, v in cache.items() if k != "block_tables"}

    def wsc(x, key):
        if shard_specs is None:
            return x
        return jax.lax.with_sharding_constraint(x, shard_specs[key])

    bt0 = wsc(bt0, "table")

    def body(carry, xs):
        pools, ct, pos, active, bt = carry
        key, k = xs
        c = dict(pools)
        c["block_tables"] = bt
        # a frozen lane of the latent path decodes at position 0: its
        # pooled prefix is empty, so the kernel reads none of its pages
        pos_in = jnp.where(active, pos, 0) if cfg.use_mla else pos
        out = decode_step(params, cfg, ct[:, None], pos_in, c,
                          window_len=window_len, use_kernel=use_kernel,
                          shard_specs=shard_specs)
        nt, conf = sample_fn(key, out["logits"])
        if score_fn is not None:
            scores = score_fn(out["hidden"])
        else:
            scores = jnp.zeros((B,), jnp.float32)
        scores = wsc(scores, "lane")
        token_valid = active
        # the hidden state belongs to the input token; boundary => the
        # previous token closed a reasoning step
        score_valid = active & (ct == step_id)
        nt = jnp.where(active, nt, ct)  # frozen lanes re-feed their token
        nt, conf = wsc(nt, "lane"), wsc(conf, "lane")
        new_active = wsc(active & (nt != eos_id) & (k + 1 < limits), "lane")
        new_pos = wsc(pos + active.astype(pos.dtype), "lane")
        new_bt = wsc(jnp.where(new_active[:, None], bt, scratch_block),
                     "table")
        new_pools = out["cache"]
        new_pools.pop("block_tables", None)
        if shard_specs is not None:
            new_pools = {
                k_: jax.lax.with_sharding_constraint(
                    v, shard_specs["pools"][k_])
                for k_, v in new_pools.items()}
        ys = (nt, conf, scores, token_valid, score_valid)
        if cfg.uses_moe:     # the running lanes' pairs per held expert
            ys = ys + (jnp.sum(jnp.where(active[:, None],
                                         out["expert_load"], 0), axis=0),)
        return (new_pools, nt, new_pos, new_active, new_bt), ys

    carry0 = (pools, tokens, positions, active0, bt0)
    (pools, ct, pos, _, _), ys = jax.lax.scan(
        body, carry0, (rng_keys, jnp.arange(horizon)))
    toks, confs, scores, tok_valid, score_valid = ys[:5]
    out = {
        "tokens": toks.T, "confidences": confs.T, "scores": scores.T,
        "token_valid": tok_valid.T, "score_valid": score_valid.T,
        "final_tokens": ct, "positions": pos, "cache": pools,
    }
    if cfg.uses_moe:
        out["expert_load"] = jnp.sum(ys[5], axis=0)
    return out


# ---------------------------------------------------------------------------
# chunked prefill against the paged cache (continuous-batching engine)
# ---------------------------------------------------------------------------

def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill is implemented for the paged-attention dense/MoE
    stack, GQA and latent (MLA). Recurrent (SSM/hybrid) archs would need
    the mixer to accept an initial state per chunk, and enc-dec the cross
    cache — those fall back to one-shot prefill in the engine."""
    return (cfg.arch_type not in ("ssm", "hybrid")
            and not cfg.is_encoder_decoder)


def prefill_chunk_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                       positions: jax.Array, valid: jax.Array, cache: dict,
                       window_len: int, use_kernel: bool = False,
                       shard_specs=None) -> dict:
    """Prefill one prompt chunk into the paged KV cache.

    tokens [B, C] (right-padded to the static chunk width); positions
    [B, C] absolute prompt positions; valid [B, C] marks real tokens.
    Earlier chunks' KV must already be in the pool (previous calls).
    Returns {logits [B, C, V], cache} — the caller samples from the
    logits at the prompt's last valid position of the final chunk.

    ``use_kernel`` routes the chunk attention through the multi-query
    Pallas paged kernel instead of materializing the dense
    [B, KVH, G, C, bp*bs + C] score tensor per layer.
    """
    assert supports_chunked_prefill(cfg), cfg.arch_type
    if cfg.use_mla:
        return _latent_chunk_step(params, cfg, tokens, positions, valid,
                                  cache, window_len, use_kernel)
    new_cache = dict(cache)
    window = cfg.sliding_window
    quant = "k_scale" in cache  # quantized paged pool (int8/fp8)
    pool_spec = (None if shard_specs is None
                 else shard_specs["layer_pool"].get("k_pool"))
    scale_spec = (None if shard_specs is None
                  else shard_specs["layer_pool"].get("k_scale"))
    act = None if shard_specs is None else shard_specs["prefill_act"]
    kmesh = (shard_specs["lane"].mesh
             if use_kernel and shard_specs is not None else None)

    def wsc_h(x):  # see decode_step: keep the residual carry pinned
        if act is None:
            return x
        return jax.lax.with_sharding_constraint(x, act)

    h = wsc_h(_embed(params, cfg, tokens))  # [B, C, D]

    def body(h, xs):
        if quant:
            lp, k_pool, v_pool, ksc, vsc = xs
        else:
            (lp, k_pool, v_pool), ksc, vsc = xs, None, None
        a_in = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        res = L.gqa_attention_prefill_chunk(
            lp["attn"], cfg, a_in, positions, valid, k_pool, v_pool,
            cache["block_tables"], window_len, window=window,
            use_kernel=use_kernel, kernel_mesh=kmesh,
            pool_spec=pool_spec, act_spec=act,
            k_scale=ksc, v_scale=vsc, scale_spec=scale_spec)
        a, pools = res[0], res[1:]
        h = h + a
        m_in = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
        if cfg.uses_moe:
            m, _, _ = L.moe_dropless(lp["moe"], cfg, m_in,
                                     use_kernel=use_kernel)
        else:
            m = L.swiglu(lp["mlp"], m_in, act_spec=act)
        return wsc_h(h + m), pools

    if cfg.first_k_dense:
        raise NotImplementedError(
            "leading dense layers are served on the latent (MLA) path")
    xs = (params["layers"], cache["k_pool"], cache["v_pool"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    h, pools = jax.lax.scan(body, h, xs)
    new_cache["k_pool"], new_cache["v_pool"] = pools[:2]
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = pools[2:4]
    hidden = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, hidden)
    return {"logits": logits, "hidden": hidden, "cache": new_cache}


def _latent_chunk_step(params, cfg, tokens, positions, valid, cache,
                       window_len, use_kernel):
    """``prefill_chunk_step`` over the latent pool: each layer attends
    absorbed over the pooled prefix (read only) and the chunk's own
    entries; the chunk's entries are written into the pool in place after
    the layer scan. The attention runs in XLA; the routed experts take the
    Pallas ``expert_ffn`` with ``use_kernel``."""
    pool, bt = cache["kv_pool"], cache["block_tables"]

    def attend(p, a_in, i):
        return L.mla_attention_prefill_chunk(p, cfg, a_in, positions, valid,
                                             pool, i, bt)

    h, entries, _ = _latent_layers(params, cfg, _embed(params, cfg, tokens),
                                   attend, use_kernel)
    new_cache = dict(cache)
    new_cache["kv_pool"] = _write_latent(cfg, pool, bt, positions % window_len,
                                         entries, valid)
    hidden = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, hidden)
    return {"logits": logits, "hidden": hidden, "cache": new_cache}


# ---------------------------------------------------------------------------
# cache construction / prefill population (serving engine path)
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, capacity: int,
                      num_blocks: Optional[int] = None,
                      encoder_len: Optional[int] = None,
                      kv_dtype: str = "bf16") -> dict:
    """Zeroed decode cache. ``capacity`` = per-sequence token capacity
    (the window). ``num_blocks`` sizes the shared pool; defaults to
    batch * blocks_per_seq (dedicated blocks). ``kv_dtype`` selects the
    paged-pool storage (``f32|bf16|int8|fp8``; see ``models.kv_quant``);
    quantized dtypes add ``k_scale``/``v_scale`` entries with one f32
    scale per (layer, page, KV head). Recurrent and cross-attention
    state always stays full precision."""
    bs = cfg.kv_block_size
    bp = -(-capacity // bs)
    nb = num_blocks if num_blocks is not None else batch * bp
    attn = cfg.attention_layer_ids()
    dt = jnp.bfloat16
    pool_dt = kv_quant.kv_pool_dtype(kv_dtype)
    cache: dict = {}
    if attn:
        la = len(attn)
        if cfg.use_mla:
            # MLA latent pool: f32/bf16 only (quantized dtypes are
            # rejected upstream by kv_quant.resolve_kv_dtype)
            cache["kv_pool"] = jnp.zeros(
                (la, nb, bs, cfg.latent_width),
                pool_dt if kv_dtype in ("f32", "bf16") else dt)
        else:
            cache["k_pool"] = jnp.zeros(
                (la, nb, bs, cfg.num_kv_heads, cfg.head_dim), pool_dt)
            cache["v_pool"] = jnp.zeros(
                (la, nb, bs, cfg.num_kv_heads, cfg.head_dim), pool_dt)
            scales = kv_quant.init_scales(cfg, nb, kv_dtype)
            if scales is not None:
                # distinct buffers: the jitted steps donate the whole
                # cache dict, and XLA rejects donating one buffer twice
                cache["k_scale"] = scales
                cache["v_scale"] = scales + 0.0
        # default: sequence b owns blocks [b*bp, (b+1)*bp)
        cache["block_tables"] = (
            jnp.arange(batch * bp, dtype=jnp.int32).reshape(batch, bp)
            % max(nb, 1))
    if cfg.arch_type in ("ssm", "hybrid"):
        cache["ssm_state"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim,
             cfg.ssm_state_size), jnp.float32)
        cache["conv_state"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.ssm_conv_width - 1,
             cfg.d_inner + 2 * cfg.ssm_state_size), dt)
    if cfg.is_encoder_decoder:
        T = encoder_len or cfg.encoder_seq_len or 1024
        la = len(attn)
        cache["cross_k"] = jnp.zeros(
            (la, batch, T, cfg.num_kv_heads, cfg.head_dim), dt)
        cache["cross_v"] = jnp.zeros(
            (la, batch, T, cfg.num_kv_heads, cfg.head_dim), dt)
    return cache


def build_cross_cache(params: dict, cfg: ModelConfig, enc_out: jax.Array):
    """Compute per-decoder-layer cross-attention K/V from encoder output."""
    def body(_, lp):
        k, v = L.cross_kv(lp["cross"], cfg, enc_out)
        return None, (k, v)
    _, (ck, cv) = jax.lax.scan(body, None, params["layers"])
    return ck, cv


def write_prefill_kv(cfg: ModelConfig, cache: dict, kvs,
                     seq_lens: jax.Array) -> dict:
    """Scatter prefill K/V (from forward_full(return_kv=True)) into the
    paged pools. Assumes prompt_len <= capacity (slot = position)."""
    cache = dict(cache)
    bt = cache.get("block_tables")
    bs = cfg.kv_block_size

    def scatter(pool, values):
        # pool [L*, NB, bs, ...]; values [L*, B, S, ...]
        Bn, S = values.shape[1], values.shape[2]
        pos = jnp.broadcast_to(jnp.arange(S)[None], (Bn, S))
        block_ids = jnp.take_along_axis(bt, pos // bs, axis=1)  # [B,S]
        offs = pos % bs
        valid = pos < seq_lens[:, None]
        # route invalid writes to a scratch copy of position 0 write? use
        # where on values and clamp ids; overwriting beyond len is harmless
        # because attention masks by cache_lens.
        vals = jnp.moveaxis(values, 0, 2)  # [B,S,L*,...] -> scatter per B,S
        pool_t = jnp.moveaxis(pool, 0, 2)  # [NB,bs,L*,...]
        pool_t = pool_t.at[block_ids, offs].set(vals)
        return jnp.moveaxis(pool_t, 2, 0)

    if cfg.arch_type == "ssm":
        ss, cs = kvs
        cache["ssm_state"], cache["conv_state"] = ss, cs
        return cache
    if cfg.arch_type == "hybrid":
        (ss, cs), (k, v) = kvs
        cache["ssm_state"] = ss.reshape(-1, *ss.shape[2:])
        cache["conv_state"] = cs.reshape(-1, *cs.shape[2:])
        cache["k_pool"] = scatter(cache["k_pool"], k)
        cache["v_pool"] = scatter(cache["v_pool"], v)
        return cache
    if cfg.use_mla:
        # kvs [La, B, S, E]: every layer's entries of a position in one
        # scatter, in place
        Bn, S = kvs.shape[1], kvs.shape[2]
        pos = jnp.broadcast_to(jnp.arange(S)[None], (Bn, S))
        cache["kv_pool"] = _write_latent(cfg, cache["kv_pool"], bt, pos, kvs)
        return cache
    k, v = kvs
    if "k_scale" in cache:
        # quantized pool: each token quantizes against its own per-head
        # absmax (kv_quant.quantize_pages), then codes and scales
        # scatter through the same indexing — the one-shot write is
        # slot-for-slot identical to the chunked/decode write paths.
        qd = cache["k_pool"].dtype
        qk, sk = kv_quant.quantize_pages(k, qd)  # [L*,B,S,KVH,hd]/[...,KVH]
        qv, sv = kv_quant.quantize_pages(v, qd)
        cache["k_pool"] = scatter(cache["k_pool"], qk)
        cache["k_scale"] = scatter(cache["k_scale"], sk)
        cache["v_pool"] = scatter(cache["v_pool"], qv)
        cache["v_scale"] = scatter(cache["v_scale"], sv)
        return cache
    cache["k_pool"] = scatter(cache["k_pool"], k)
    cache["v_pool"] = scatter(cache["v_pool"], v)
    return cache


def copy_kv_block(cfg: ModelConfig, cache: dict, src: jax.Array,
                  dst: jax.Array) -> dict:
    """Device-side copy of one paged KV block: pool[:, dst] = pool[:, src].

    The copy-on-write step behind prefix sharing: when the engine must
    write into a block whose refcount is > 1, it allocates ``dst``, copies
    the shared contents, and repoints the writer's block table. Only the
    paged attention pools are touched; per-slot recurrent state (SSM/conv)
    is not block-addressed and needs no COW. ``src``/``dst`` may be traced
    scalars so a single jitted instance serves every block pair.
    """
    cache = dict(cache)
    # per-page quant scales are block-addressed too: they ride the COW
    # copy verbatim (the copied page's codes stay valid under its scale)
    for key in ("k_pool", "v_pool", "kv_pool", "k_scale", "v_scale"):
        if key in cache:
            pool = cache[key]
            cache[key] = pool.at[:, dst].set(pool[:, src])
    return cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

# Above this token count the [B, S, V] fp32 logits (plus softmax
# temporaries) dominate training HBM — e.g. 622 GB global for qwen3's
# 152k vocab at train_4k. The loss then switches to a sequence-chunked
# rematerialised cross-entropy: per-chunk logits are recomputed in the
# backward pass, so only the [B, S, D] hidden survives.
CHUNKED_CE_THRESHOLD = 1024
CE_CHUNK = 256


def _chunked_ce(hidden: jax.Array, w: jax.Array, labels: jax.Array,
                valid: jax.Array, chunk: int) -> tuple:
    """hidden [B,S,D]; w [D,V]; labels/valid [B,S]. Returns (nll_sum, n)."""
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    hs = jnp.moveaxis(hidden.reshape(B, nc, chunk, D), 1, 0)
    ys = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    ms = jnp.moveaxis(valid.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(acc, inp):
        h_c, y_c, m_c = inp
        logits = (h_c @ w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        safe = jnp.where(m_c, y_c, 0)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m_c
        return acc + jnp.sum(nll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ys, ms))
    return total


def lm_loss(params: dict, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, aux_weight: float = 0.01,
            use_kernel: bool = False,
            modality_embeds: Optional[jax.Array] = None,
            encoder_embeds: Optional[jax.Array] = None,
            remat: bool = False) -> jax.Array:
    out = forward_full(params, cfg, tokens, use_kernel=use_kernel,
                       modality_embeds=modality_embeds,
                       encoder_embeds=encoder_embeds, remat=remat)
    valid = (labels >= 0) & (labels < cfg.vocab_size)
    S = tokens.shape[1]
    if S > CHUNKED_CE_THRESHOLD:
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        nll_sum = _chunked_ce(out["hidden"], w, labels, valid, CE_CHUNK)
        loss = nll_sum / jnp.maximum(jnp.sum(valid), 1)
        return loss + aux_weight * out["aux_loss"]
    logits = out["logits"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    safe = jnp.where(valid, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
    return loss + aux_weight * out["aux_loss"]
