"""Paged attention (THE serving hot spots) as Pallas kernels.

Queries attend over a sequence's KV blocks, looked up through a block
table — the exact memory layout the STEP pruning policy manages (pruning
a trace returns its blocks to this pool). Three faces:

  * DECODE (``paged_attention``): one new query token per sequence,
    attending over the pooled cache only — what the fused
    ``decode_horizon`` scan calls once per layer and iteration;
  * CHUNKED PREFILL (``paged_attention_prefill``): a chunk of C query
    tokens per sequence attends over the pooled prefix (earlier chunks,
    masked to slots strictly before the chunk) PLUS the chunk's own
    exact KV with causal-within-chunk masking, per-token validity (the
    final chunk is right-padded) and optional sliding-window masking —
    replacing the dense ``[B, KVH, G, C, bp*bs + C]`` score tensor the
    jnp fallback materializes per layer;
  * LATENT DECODE (``paged_latent_attention``): MLA's absorbed decode
    over the latent pool.

TPU adaptation of vLLM's GPU PagedAttention:
  * the block table and per-sequence lengths are SCALAR-PREFETCHED
    (SMEM) so the kernel can compute data-dependent block indices before
    the body runs — the TPU-idiomatic replacement for pointer-chasing;
  * K/V pools stay in HBM (``memory_space=ANY``), in their stored
    ``[NB, page, KVH, hd]`` layout, and reach VMEM only by DMA of WHOLE
    pages ``[page, KVH, hd]`` (plus their scale rows on a quantized
    pool). Whole pages, because a DMA of one head's ``[page, hd]`` tile
    slices the pool's second-minor (KVH) dimension below the chip's
    (8, 128) tiling, which the compiler refuses; each page is therefore
    read from HBM once per sequence, not once per (sequence, kv head);
  * only pages holding visible slots are fetched, so a short sequence
    in a wide table costs its own pages — the dense path pays for every
    slot.

The decode face walks a lane's live pages ``P`` at a time
(``decode_pages_per_step``: 128 tokens a step, P = 8 at page 16), one
DMA per page into a two-slot buffer of ``[P * page, KVH, hd]`` rows for
K and for V: 2 x 2 x 256 KB at Qwen3-4B's 8 kv heads, hd = 128, bf16.
The next step's pages — at a lane's last step, the next lane's first —
are started before the current step's are waited on, so lanes run in
order. A step's K and V are relaid out once, to ``[KVH, P * page, hd]``
(256 KB each at those shapes, plus the f32 copy of V); each kv head
then scores its G query heads against the step's 128 tokens in one
lane-dense [G, 128] tile, batched over the heads, and the [KVH, G, ...]
softmax state rides the step loop. Slicing one head's rows out of each
token's ``[KVH, hd]`` tile instead costs a load, rotate and repack per
row and head. No head's arithmetic depends on the
others, so the mesh's shard-local call over a slice of the kv heads
gives that slice's output bit for bit.

The prefill face walks one page at a time through 2 x 2 pages (K and V,
double-buffered). Its KV heads are an unrolled (static) loop — a static
head index into the VMEM page is what the compiler can prove aligned —
and all G = H // KVH query heads of a kv head and the chunk's C tokens
form one [C*G, hd] tile. The query/output tiles and the f32 softmax
state (m, l, acc) of every kv head sit in VMEM — about 6 MB for a
64-token chunk at 32/8 heads and hd = 128, under the 16 MB scoped
budget.

Numerics contract (pinned by tests against the dense path):
  * scores are q . k accumulated in f32: the decode face feeds the MXU
    the pool dtype (f32 where a page is dequantized), as the dense
    path's einsum does, the prefill face f32 casts (a bf16 product is
    exact in f32 either way); softmax, probabilities and the PV
    accumulation stay f32;
  * quantized pages are dequantized inside the page loop (cast to f32,
    times the slot's scale), the same math the dense fallback applies;
  * empty cache (``cache_len == 0`` and no visible own tokens) emits
    ZEROS via the ``safe_l`` guard — the convention the dense paths
    now share (a bare softmax over all -1e30 scores would average
    garbage KV instead).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mq_paged_kernel(*refs, scale: float, page_size: int, num_pages: int,
                     groups: int, window: Optional[int], has_scales: bool):
    """Chunked-prefill body, one grid step per sequence ``b``. ``refs``
    layout (scalar prefetch first, scratch last):

      bt, lens, nvalid, q, k_pool, v_pool, [k_scale, v_scale,]
      own_k, own_v, o, k_buf, v_buf, [ks_buf, vs_buf,] sem, m, l, acc

    ``lens[b]`` = number of valid pooled slots. Prefill never wraps
    (slot == position), so this doubles as the chunk's start position:
    query c sits at absolute position ``lens[b] + c``.

    ``has_scales`` marks a quantized (int8/fp8) pool: ``k_scale``/
    ``v_scale`` arrive as lane-dense [NB, 1, KVH * page] f32 rows in HBM
    (``_mq_paged_call`` relayouts the pool's [NB, page, KVH]) and ride
    the same page DMA; each page is dequantized right inside the
    online-softmax loop — cast to f32, multiply by its per-(slot,
    kv-head) scale column — the identical math the dense fallback
    applies to its gathered pages.
    """
    refs = list(refs)
    bt_ref, lens_ref, nvalid_ref = refs[:3]
    q_ref, k_pool_ref, v_pool_ref = refs[3:6]
    i = 6
    hbm = [k_pool_ref, v_pool_ref]
    if has_scales:
        hbm += refs[i:i + 2]
        i += 2
    own_k_ref, own_v_ref = refs[i], refs[i + 1]
    i += 2
    o_ref = refs[i]
    bufs = refs[i + 1:i + 1 + len(hbm)]
    sem, m_s, l_s, acc_s = refs[i + 1 + len(hbm):]
    k_buf, v_buf = bufs[0], bufs[1]
    kvh = q_ref.shape[1]
    b = pl.program_id(0)

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    cache_len = lens_ref[b]

    def online_update(h, s, mask, v):
        """Fold one masked [C*G, S_blk] score tile of kv head ``h`` and
        its [S_blk, hd] values into that head's softmax state."""
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[h]                        # [C*G, 1]
        l_prev = l_s[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        acc_s[h] = acc_s[h] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[h] = m_new
        l_s[h] = alpha * l_prev + jnp.sum(pexp, axis=-1, keepdims=True)

    def scores(h, k):
        q = q_ref[0, h].astype(jnp.float32)    # [C*G, hd]
        return jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    # ---- pooled-prefix pages: [lo, hi) are the pages with a visible slot
    hi = jnp.minimum((cache_len + page_size - 1) // page_size, num_pages)
    lo = 0
    if window is not None:
        # the loosest query (chunk-local c = 0, position cache_len) sees
        # slots > cache_len - window; pages entirely left of that are
        # dead for every query in the chunk
        lo = jnp.maximum(cache_len - window, 0) // page_size

    def page_copies(p, slot):
        block_id = bt_ref[b, p]
        return [pltpu.make_async_copy(src.at[block_id], buf.at[slot],
                                      sem.at[slot])
                for src, buf in zip(hbm, bufs)]

    @pl.when(lo < hi)
    def _first_fetch():
        for cp in page_copies(lo, lo % 2):
            cp.start()

    def page_step(p, carry):
        slot = p % 2

        @pl.when(p + 1 < hi)
        def _prefetch_next():
            for cp in page_copies(p + 1, 1 - slot):
                cp.start()

        for cp in page_copies(p, slot):
            cp.wait()

        page_start = p * page_size
        shape = (q_ref.shape[2], page_size)
        slot_pos = page_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = slot_pos < cache_len
        c = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // groups
        mask &= c < nvalid_ref[b]              # padded queries emit zeros
        if window is not None:
            mask &= slot_pos > (cache_len + c - window)
        for h in range(kvh):
            k = k_buf[slot, :, h, :].astype(jnp.float32)   # [page, hd]
            v = v_buf[slot, :, h, :].astype(jnp.float32)
            if has_scales:                     # in-loop dequantization
                cols = slice(h * page_size, (h + 1) * page_size)
                k = k * bufs[2][slot][:, cols].T           # [page, 1]
                v = v * bufs[3][slot][:, cols].T
            online_update(h, scores(h, k), mask, v)
        return carry

    jax.lax.fori_loop(lo, hi, page_step, 0)

    # ---- the chunk's own exact KV --------------------------------------
    C = own_k_ref.shape[2]
    shape = (q_ref.shape[2], C)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // groups
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    nv = nvalid_ref[b]
    mask = (j <= c) & (j < nv) & (c < nv)      # causal + no pad
    if window is not None:
        mask &= j > (c - window)
    for h in range(kvh):
        k = own_k_ref[0, h].astype(jnp.float32)       # [C, hd]
        v = own_v_ref[0, h].astype(jnp.float32)
        online_update(h, scores(h, k), mask, v)

    l = l_s[...]
    safe_l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows -> zeros
    o_ref[0] = (acc_s[...] / safe_l).astype(o_ref.dtype)


def _mq_paged_call(qf, k_pool, v_pool, block_tables, lens, nvalid,
                   own_k, own_v, *, scale, window, interpret,
                   k_scale=None, v_scale=None):
    """Dispatch the chunked-prefill kernel. qf [B, KVH, C*G, hd]
    (flattened query tile); own_k/own_v [B, KVH, C, hd]; k_scale/v_scale
    [NB, page, KVH] f32 or None (full-precision pool)."""
    B, KVH, CG, hd = qf.shape
    page_size = k_pool.shape[1]
    bp = block_tables.shape[1]
    has_scales = k_scale is not None
    C = own_k.shape[2]
    groups = CG // C

    kernel = functools.partial(
        _mq_paged_kernel, scale=scale, page_size=page_size, num_pages=bp,
        groups=groups, window=window, has_scales=has_scales)

    def per_seq(shape):
        return pl.BlockSpec((1, *shape), lambda b, *_: (b, 0, 0, 0))

    hbm = [k_pool, v_pool]
    if has_scales:
        # [NB, page, KVH] -> [NB, 1, KVH * page]: one lane-dense row per
        # block. A KVH-wide minor dim is padded to 128 lanes in HBM, and
        # a DMA cannot slice a block out of that padded layout.
        hbm += [s.transpose(0, 2, 1).reshape(s.shape[0], 1, -1)
                for s in (k_scale, v_scale)]
    operands = [block_tables, lens, nvalid, qf] + hbm + [own_k, own_v]
    in_specs = [per_seq((KVH, CG, hd))]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm)
    in_specs += [per_seq((KVH, C, hd))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=per_seq((KVH, CG, hd)),
        scratch_shapes=[pltpu.VMEM((2, *x.shape[1:]), x.dtype) for x in hbm]
        + [pltpu.SemaphoreType.DMA((2,)),
           pltpu.VMEM((KVH, CG, 1), jnp.float32),
           pltpu.VMEM((KVH, CG, 1), jnp.float32),
           pltpu.VMEM((KVH, CG, hd), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, CG, hd), qf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit,
                   static_argnames=("scale", "window", "interpret"))
def paged_attention_prefill(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            prefix_lens: jax.Array, num_valid: jax.Array,
                            own_k: jax.Array, own_v: jax.Array, *,
                            scale: float, window: Optional[int] = None,
                            interpret: bool = False,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None
                            ) -> jax.Array:
    """Chunked prefill: q [B, C, H, hd] attends over the pooled prefix
    plus the chunk's own exact (un-roundtripped) KV.

    prefix_lens [B]: pooled tokens strictly before this chunk — also the
    chunk's start position (prefill never wraps: slot == position, which
    the engine gates chunked prefill on). Query c of row b sits at
    absolute position ``prefix_lens[b] + c`` (positions are contiguous
    across the chunk, including right-padding). num_valid [B]: real
    (non-padded) tokens; padded queries emit zeros and padded own-KV
    columns are masked. own_k/own_v [B, C, KVH, hd]. ``k_scale``/
    ``v_scale`` [NB, page, KVH] dequantize an int8/fp8 pool in-loop (the
    chunk's own KV is exact and never scaled). Returns [B, C, H, hd].
    """
    B, C, H, hd = q.shape
    KVH = k_pool.shape[2]
    G = H // KVH
    # [B, KVH, C*G, hd]: chunk tokens x groups of one kv head in one tile
    qf = q.reshape(B, C, KVH, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KVH, C * G, hd)
    ok = own_k.transpose(0, 2, 1, 3)  # [B, KVH, C, hd]
    ov = own_v.transpose(0, 2, 1, 3)
    out = _mq_paged_call(qf, k_pool, v_pool, block_tables,
                         prefix_lens, num_valid, ok, ov,
                         scale=scale, window=window, interpret=interpret,
                         k_scale=k_scale, v_scale=v_scale)
    return out.reshape(B, KVH, C, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, hd)


# ---------------------------------------------------------------------------
# decode face: one query token per lane, several pages per DMA step
# ---------------------------------------------------------------------------

DECODE_STEP_TOKENS = 128      # tokens per step: one lane-dense score tile
DECODE_BUFFER_BYTES = 4 << 20  # VMEM for K and V, both slots


def decode_pages_per_step(page: int, bp: int, page_bytes: int) -> int:
    """Pages a decode step fetches: ``DECODE_STEP_TOKENS`` tokens, fewer
    where the four page buffers (K and V, two slots) would pass
    ``DECODE_BUFFER_BYTES``, and never more than the table holds."""
    pps = max(1, DECODE_STEP_TOKENS // page)
    pps = min(pps, max(1, DECODE_BUFFER_BYTES // (4 * page_bytes)))
    return min(pps, bp)


def _decode_kernel(bt_ref, lens_ref, q_ref, *refs, scale: float, page: int,
                   num_pages: int, pps: int, has_scales: bool):
    """One grid step per lane ``b``; ``refs`` are the pools (and scale
    rows), the output, their step buffers, the DMA semaphores and the
    slot of the lane's first step.

    A lane's live pages arrive ``pps`` at a time into a two-slot buffer
    of ``[pps * page, KVH, hd]`` rows, one DMA per page; the next step's
    pages, or at a lane's last step the next lane's first, are started
    before this step's are waited on, so the page stream does not stop
    between lanes (lanes therefore run in order). A step's K and V are
    relaid out once, to ``[KVH, pps * page, hd]``; each kv head's step is
    then one [G, pps * page] score tile and one [G, hd] value dot, batched
    over the heads, folded into an online softmax held as one
    [KVH, G, ...] state. No kv head's arithmetic depends on how many
    others the call holds, so a call over a slice of the heads (the
    mesh's shard-local call) gives that slice's output bit for bit."""
    n_hbm = 4 if has_scales else 2
    hbm = refs[:n_hbm]
    o_ref = refs[n_hbm]
    bufs = refs[n_hbm + 1:2 * n_hbm + 1]
    sem, base_ref = refs[2 * n_hbm + 1:]
    k_buf, v_buf = bufs[0], bufs[1]
    b = pl.program_id(0)
    last = pl.num_programs(0) - 1
    kvh = q_ref.shape[1]
    rows = pps * page                              # tokens a step
    f32 = jnp.float32

    def live_pages(lane):
        return jnp.minimum((lens_ref[lane] + page - 1) // page, num_pages)

    def fetch(lane, c, slot, hi, op):
        """Start or wait for the pages of step ``c`` of ``lane`` below
        ``hi``: K and V pages into rows ``j * page``, scale rows into
        row ``j``."""
        for j in range(pps):
            @pl.when(c * pps + j < hi)
            def _():
                block = bt_ref[lane, c * pps + j]
                for src, buf in zip(hbm, bufs):
                    w = src.shape[1]
                    cp = pltpu.make_async_copy(
                        src.at[block], buf.at[slot, pl.ds(j * w, w)],
                        sem.at[slot])
                    cp.start() if op == "start" else cp.wait()

    n = lens_ref[b]
    hi = live_pages(b)
    steps = (hi + pps - 1) // pps
    nxt = jnp.minimum(b + 1, last)
    hi_next = jnp.where(b < last, live_pages(nxt), 0)

    @pl.when(b == 0)
    def _first():
        # rows past a lane's last page are never fetched into a step:
        # they keep earlier pool content (masked, and finite)
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        base_ref[0] = 0
        fetch(b, 0, 0, hi, "start")

    base = base_ref[0]
    q = q_ref[0]                                   # [KVH, G, hd]
    # scores multiply q and k in their common dtype (a quantized pool's
    # pages are dequantized to f32 first), accumulating in f32
    dt = f32 if has_scales else jnp.promote_types(q.dtype, k_buf.dtype)

    def heads(buf, slot):
        """A step's [rows, KVH, hd] pages as [KVH, rows, hd]."""
        x = buf[slot]
        return jnp.swapaxes(x.astype(f32) if has_scales else x, 0, 1)

    def scales(buf, slot):
        """[KVH, rows, 1] f32 scales of a step's rows."""
        return jnp.stack([
            jnp.concatenate([buf[slot, j][:, h * page:(h + 1) * page].T
                             for j in range(pps)])
            for h in range(kvh)])

    def step(c, carry):
        m_prev, l_prev, acc = carry
        slot = (base + c) % 2

        @pl.when(c + 1 < steps)
        def _next_step():
            fetch(b, c + 1, 1 - slot, hi, "start")

        @pl.when(c + 1 == steps)
        def _next_lane():
            fetch(nxt, 0, 1 - slot, hi_next, "start")

        fetch(b, c, slot, hi, "wait")
        k = heads(k_buf, slot)
        v = heads(v_buf, slot).astype(f32)
        if has_scales:                              # in-loop dequantization
            k = k * scales(bufs[2], slot)
            v = v * scales(bufs[3], slot)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=f32) * scale     # [KVH, G, rows]
        mask = c * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) < n
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=f32)
        return (m_new, alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + pv)

    state = (jnp.full((*q.shape[:2], 1), NEG_INF, f32),
             jnp.zeros((*q.shape[:2], 1), f32), jnp.zeros(q.shape, f32))
    _, l, acc = jax.lax.fori_loop(0, steps, step, state)

    @pl.when(steps == 0)
    def _empty_lane():
        fetch(nxt, 0, base, hi_next, "start")

    base_ref[0] = (base + steps) % 2
    safe_l = jnp.where(l == 0.0, 1.0, l)          # empty rows -> zeros
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, cache_lens: jax.Array, *,
                    scale: float, interpret: bool = False,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Decode: q [B, H, hd]; pools [NB, page, KVH, hd]; block_tables
    [B, bp]; cache_lens [B] valid slots. Returns [B, H, hd].

    What the fused ``decode_horizon`` scan invokes once per iteration.
    ``cache_len == 0`` rows emit zeros (the engine's dead-slot
    convention). ``k_scale``/``v_scale`` [NB, page, KVH] dequantize an
    int8/fp8 pool in-loop."""
    B, H, hd = q.shape
    NB, page, KVH, _ = k_pool.shape
    G = H // KVH
    bp = block_tables.shape[1]
    pps = decode_pages_per_step(
        page, bp, page * KVH * hd * k_pool.dtype.itemsize)
    hbm = [k_pool, v_pool]
    if k_scale is not None:
        # [NB, page, KVH] -> [NB, 1, 1, KVH * page]: one lane-dense row
        # per block, head-major, its own major dim to land in
        hbm += [s.transpose(0, 2, 1).reshape(NB, 1, 1, KVH * page)
                for s in (k_scale, v_scale)]
    kernel = functools.partial(
        _decode_kernel, scale=scale, page=page, num_pages=bp, pps=pps,
        has_scales=k_scale is not None)
    # [B, KVH, G, hd]: all G query heads of a kv head form one MXU tile
    lane = pl.BlockSpec((1, KVH, G, hd), lambda b, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[lane] + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
        out_specs=lane,
        scratch_shapes=[pltpu.VMEM((2, pps * x.shape[1], *x.shape[2:]),
                                   x.dtype) for x in hbm]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a lane's last step fetches the next lane's first pages
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, cache_lens, q.reshape(B, KVH, G, hd), *hbm)
    return out.reshape(B, H, hd)


# ---------------------------------------------------------------------------
# latent face: absorbed MLA decode over the latent pool
# ---------------------------------------------------------------------------

LATENT_PAGES_PER_STEP = 8     # pages per DMA step (128 tokens at page 16)


def _latent_kernel(layer_ref, bt_ref, pos_ref, q_ref, own_ref, pool_ref,
                   o_ref, buf, sem, m_s, l_s, acc_s, *, scale: float,
                   page: int, num_pages: int, pps: int, window: int,
                   out_dim: int):
    """One grid step per lane ``b``: all H heads of the lane at once.

    The lane's pooled tokens are ``min(pos, window)`` slots (minus the
    slot the current token will overwrite once the window has rolled);
    its own entry starts the online softmax, then its live pages arrive
    ``pps`` at a time, one DMA each into a double buffer, while the
    previous chunk is computed on. A chunk's scores are one dot of the
    [H, E] query against the [pps * page, E] entries (E = kv_lora + rope,
    the rope part included), its values one dot of the probabilities
    against the same entries; the last E - out_dim columns are dropped at
    the end."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    n = jnp.minimum(pos, window)
    skip = jnp.where(pos >= window, pos % window, -1)
    f32 = jnp.float32
    q = q_ref[0]                                   # [H, E]
    rows = pps * page

    @pl.when(b == 0)
    def _clear():
        # pages past a lane's last one are never fetched into a chunk:
        # their rows keep earlier pool content (masked, but finite)
        buf[...] = jnp.zeros_like(buf)

    own = own_ref[0].astype(f32)                   # [1, E]
    m_s[...] = jnp.sum(q.astype(f32) * own, axis=-1, keepdims=True) * scale
    l_s[...] = jnp.ones_like(l_s)
    acc_s[...] = jnp.broadcast_to(own, acc_s.shape)

    hi = jnp.minimum((n + page - 1) // page, num_pages)
    chunks = (hi + pps - 1) // pps

    def copy(c, j, slot):
        block_id = bt_ref[b, c * pps + j]
        return pltpu.make_async_copy(
            pool_ref.at[layer, block_id], buf.at[slot, pl.ds(j * page, page)],
            sem.at[slot])

    def fetch(c, slot, op):
        for j in range(pps):
            @pl.when(c * pps + j < hi)
            def _():
                cp = copy(c, j, slot)
                cp.start() if op == "start" else cp.wait()

    @pl.when(chunks > 0)
    def _first():
        fetch(0, 0, "start")

    def chunk_step(c, carry):
        slot = c % 2

        @pl.when(c + 1 < chunks)
        def _next():
            fetch(c + 1, 1 - slot, "start")

        fetch(c, slot, "wait")
        k = buf[slot]                                  # [rows, E]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale        # [H, rows]
        at = c * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (at < n) & (at != skip)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_new
        return carry

    jax.lax.fori_loop(0, chunks, chunk_step, 0)
    o_ref[0] = (acc_s[:, :out_dim] / l_s[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "out_dim",
                                             "pages_per_step", "interpret"))
def paged_latent_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                           block_tables: jax.Array, positions: jax.Array,
                           own: jax.Array, *, scale: float, window: int,
                           out_dim: int,
                           pages_per_step: int = LATENT_PAGES_PER_STEP,
                           interpret: bool = False) -> jax.Array:
    """Latent decode: q [B, H, E] absorbed queries ([q_nope W_uk | q_rope],
    E = kv_lora + rope); pool [La, NB, page, E], the whole latent pool in
    HBM, read at ``layer`` ([] or [1] int32); block_tables [B, bp];
    positions [B], the current token's position (the pool holds the
    lane's earlier tokens at slot ``position % window``); own [B, E], the
    current token's entry. Returns [B, H, out_dim], the probabilities
    times the entries' first ``out_dim`` columns (the latent)."""
    B, H, E = q.shape
    page = pool.shape[2]
    bp = block_tables.shape[1]
    pps = max(1, min(pages_per_step, bp))
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    kernel = functools.partial(
        _latent_kernel, scale=scale, page=page, num_pages=bp, pps=pps,
        window=window, out_dim=out_dim)

    def lane(shape):
        return pl.BlockSpec((1, *shape), lambda b, *_: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[lane((H, E)), lane((1, E)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=lane((H, out_dim)),
        scratch_shapes=[pltpu.VMEM((2, pps * page, E), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, E), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, out_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the scratch buffer is cleared at lane 0 only: lanes run in
            # order on one core
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(layer, block_tables, positions.astype(jnp.int32), q,
      own[:, None, :].astype(q.dtype), pool)
