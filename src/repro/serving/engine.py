"""The serving engine: vLLM-V1-style continuous batching in JAX.

This is the system layer the STEP paper modifies. One engine instance holds
a statically allocated paged KV pool (the per-device HBM budget), a block
manager (the allocator whose free list defines "GPU memory full"), and a
fixed-shape jitted decode step over ``max_batch`` slots.

Scheduling semantics (paper §3, §4.2):

  * baseline engines (SC / CoT / Slim-SC / DeepConf): when the next decode
    step cannot be scheduled because the pool has no free block, a running
    trace is PREEMPTED vLLM-style — its blocks are freed and it re-enters
    the waiting queue; on resume its KV cache is RECOMPUTED (discard-and-
    recompute). The waiting queue is where the paper's 40% latency goes.
  * STEP: the policy returns the lowest-scored trace; the engine PRUNES it
    and immediately reuses its blocks. The waiting queue never forms.

Continuous batching (online arrivals): ``serve_batch`` runs a scheduler
tick loop over a ``RequestQueue`` with per-request arrival times.
Requests join the waiting pool only once their arrival time passes, so
decode keeps running between admission waves and per-request
time-to-first-token / time-per-output-token are measured against the
arrival instant (``serving/metrics.py``). With every arrival at t=0 the
tick loop degenerates to the offline batch scheduler and reproduces its
outputs token-for-token under greedy sampling.

Chunked prefill (``EngineConfig.prefill_chunk_size``): long prompts are
prefilled in fixed-size chunks against the paged pool
(``prefill_chunk_step``), drawing KV blocks chunk-by-chunk through a
``BlockManager.reserve`` reservation. While traces are decoding, each
in-flight prefill advances at most one chunk per scheduler tick, so a
long prompt no longer stalls the running decode batch; with an idle
batch the prefill runs to completion immediately. A tick's combined
prefill work is budgeted by ``EngineConfig.max_tokens_per_step``
(prefill chunks and decode tokens share the tick's token budget).
Chunking applies to the shared-prefix path of paged-attention archs
(GQA and latent); recurrent/enc-dec archs and per-trace prefills fall
back to the one-shot path.

Prefix sharing (``EngineConfig.share_prompt_prefix``, default on): all N
traces of a request decode from the *same* prompt, so the prompt KV is
computed once per request, written into shared paged blocks, and forked
into each trace's block table with refcounting. The first time a trace
writes into a still-shared block (its first generated token lands in the
prompt's partial tail block) the engine copy-on-writes that block. With
the flag off the engine reproduces the original per-trace prefill path
(N sequential prompt prefills), which is the accounting baseline for
Table 3.

Cross-request prefix cache (``EngineConfig.prefix_cache``, default on):
completed prompts' full KV blocks are parked in a radix tree
(``serving/prefix_cache.py``) instead of freed; a later request whose
prompt shares a block-aligned prefix forks the cached blocks (COW
refcounting, zero recompute) and chunk-prefills only the suffix. Cached
blocks are the lowest-priority memory in the pool: under pressure the
engine evicts LRU cache-only blocks BEFORE pruning or preempting any
live trace (evict-before-prune), so enabling the cache can only add
scheduling headroom. Per-request hit accounting (``cached_tokens``)
lands in ``RequestMetrics``.

Multi-request scheduling: traces from different requests co-exist in the
fixed-shape decode step, contend for the same block pool, and are
aggregated into per-request ``RequestResult``s. Policies act per
request: the needy trace's own request's policy decides what to prune;
baseline preemption (last-arrived running trace) is global, like vLLM's
latest-arrival eviction. Each tick the engine publishes an
``AdmissionPressure`` snapshot to every active policy, so pruning
decisions can react to queued arrivals (``PruningPolicy.observe_pressure``).

Latency accounting mirrors the paper's Table 3: every wall-clock second of
the engine loop is attributed to {prefill, decode, overhead}; every second
a trace spends runnable-but-not-running (queued after preemption, or
queued at admission because memory was full) is WAIT. Decode seconds of
the shared batched step are attributed to requests proportionally to
their running traces. Waiting for a free decode *slot* (queue longer than
``max_batch``) is not memory-induced and is not counted as WAIT.
"""
from __future__ import annotations

import copy
import dataclasses
import os
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pruning import AdmissionPressure, DeepConfPolicy, PruningPolicy
from repro.data.arithmetic import extract_answer
from repro.core.scorer import scorer_score
from repro.core.trace import Trace, TraceStatus
from repro.data.tokenizer import get_tokenizer
from repro.kernels import ops as kops
from repro.models import kv_quant
from repro.models.model import (copy_kv_block, forward_full,
                                init_decode_cache, multi_decode_step,
                                prefill_chunk_step, supports_chunked_prefill,
                                write_prefill_kv)
from repro.serving.faults import (FaultPlan, FaultStats, RecoveryConfig)
from repro.serving.kv_manager import BlockManager
from repro.serving.metrics import RequestMetrics, ServeCounters, counting
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import (SamplingParams, sample_logits,
                                    sample_logits_lanes, sample_tokens,
                                    sample_tokens_lanes, sampler_path)
from repro.serving.scheduler import (SLO, ReqState, SchedulerCore,
                                     SchedulingPolicy, SharedPrefix,
                                     default_scheduler)


def _step_jit_kw() -> dict:
    """``jax.jit`` options shared by every engine step.

    On a TPU the steps round bf16 values where the program says so. By
    default XLA may keep a fused bf16 intermediate in f32 ("excess
    precision"), and it fuses a one-chip program differently from a mesh
    program whose all-gathers materialize bf16 activations: the two then
    round differently, and a mesh engine's greedy tokens leave the
    one-chip engine's at the first near-tie. On the CPU backend the same
    option moves the two programs apart instead, so it is a TPU setting.
    """
    if jax.default_backend() != "tpu":
        return {}
    return {"compiler_options": {"xla_allow_excess_precision": False}}


# Back-compat aliases: these lived here before the scheduler split.
_SharedPrefix = SharedPrefix
_ReqState = ReqState


def _default_use_kernel():
    """``EngineConfig.use_kernel`` default, overridable via the
    ``REPRO_USE_KERNEL`` env var ("1"/"on"/"true" -> True, "auto" ->
    "auto", anything else -> False). This is how the CI kernel lane
    flips the whole engine suite onto the Pallas path (interpret mode
    on CPU) without touching test code."""
    val = os.environ.get("REPRO_USE_KERNEL", "").strip().lower()
    if val in ("1", "on", "true"):
        return True
    if val == "auto":
        return "auto"
    return False


def _default_prefix_cache():
    """``EngineConfig.prefix_cache`` default, overridable via the
    ``REPRO_PREFIX_CACHE`` env var ("0"/"off"/"false" -> off, anything
    else incl. unset -> on). The CI prefix-cache lane pins it to "1" so
    the whole engine suite runs with cross-request KV reuse active."""
    val = os.environ.get("REPRO_PREFIX_CACHE", "").strip().lower()
    return val not in ("0", "off", "false")


def _default_faults():
    """``EngineConfig.faults`` default, overridable via the
    ``REPRO_FAULTS`` env var (a fault-plan spec string, e.g.
    ``"step@2,alloc@5"`` — see ``serving/faults.py`` for the grammar;
    unset/empty -> no injection). The CI ``test-faults`` chaos lane sets
    it to run whole suites under a recoverable fault plan without
    touching test code."""
    val = os.environ.get("REPRO_FAULTS", "").strip()
    return val or None


def _default_kv_dtype():
    """``EngineConfig.kv_dtype`` default, overridable via the
    ``REPRO_KV_DTYPE`` env var (``f32|bf16|int8|fp8``; unset/empty ->
    "bf16", the historical pool dtype). The CI ``test-kv-quant`` lane
    sets it to "int8" to run the whole engine suite on quantized pools
    without touching test code. Validated against the model arch by
    ``kv_quant.resolve_kv_dtype`` at engine construction."""
    return os.environ.get("REPRO_KV_DTYPE", "").strip().lower() or "bf16"


def resolve_use_kernel(setting, cfg: ModelConfig, mesh=None) -> bool:
    """Resolve ``EngineConfig.use_kernel`` (False / True / "auto") to the
    bool the jitted steps consume.

    "auto" picks the compiled Pallas kernels on TPU and the dense XLA
    path on CPU hosts — on CPU the kernels only run in interpret mode
    (the kernel body executed as traced jnp), which is a correctness
    harness, not a fast path; pass ``use_kernel=True`` to force it, as
    the CI kernel lane does. On a mesh the kernel path additionally
    needs the attention heads to divide the "model" axis so the
    shard_map routing keeps every (lane, kv head) grid cell shard-local;
    "auto" falls back to the dense path where the layout is not
    covered, an explicit ``True`` raises ``NotImplementedError`` at
    construction (never silently wrong tokens).
    """
    if setting is False or setting is None:
        return False
    if setting not in (True, "auto"):
        raise ValueError(
            f"use_kernel must be True, False or 'auto', got {setting!r}")
    # the paged kernels cover GQA paged attention (the dense/MoE/hybrid
    # attention layers) and, through the latent face, MLA's absorbed
    # decode on one device
    covered = not (cfg.use_mla and mesh is not None)
    why = "the latent (MLA) kernel face has no mesh routing"
    if covered and mesh is not None:
        model_n = mesh.shape["model"]
        covered = (cfg.num_heads % model_n == 0
                   and cfg.num_kv_heads % model_n == 0)
        why = (f"kernel-on-mesh needs num_heads ({cfg.num_heads}) and "
               f"num_kv_heads ({cfg.num_kv_heads}) divisible by the "
               f"'model' axis ({model_n}) so the shard_map paged "
               f"attention stays shard-local; use use_kernel='auto' to "
               f"fall back to the dense path on this mesh")
    if not covered:
        if setting == "auto":
            return False
        raise NotImplementedError(f"use_kernel=True: {why}")
    if setting == "auto":
        return jax.default_backend() == "tpu"
    return True


@dataclasses.dataclass
class EngineConfig:
    """Static engine resources (the 'GPU')."""
    max_batch: int = 64            # decode slots (>= trace budget N)
    num_blocks: int = 128          # paged pool blocks incl. scratch
    capacity: int = 512            # per-sequence token capacity (window)
    max_new_tokens: int = 160
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    # Pallas paged-attention path for the engine-facing attention ops
    # (fused decode + chunked prefill). False = dense jnp; True = always
    # kernel (interpret mode on CPU); "auto" = kernel on TPU, dense on
    # CPU, dense fallback on meshes the shard_map layout doesn't cover.
    # Resolved by ``resolve_use_kernel`` at engine construction.
    use_kernel: "bool | str" = dataclasses.field(
        default_factory=_default_use_kernel)
    seed: int = 0
    # Prefill the prompt once per request and fork its blocks into every
    # trace (COW on first trace-private write). False restores the
    # original per-trace prefill path (the Table-3 accounting baseline).
    share_prompt_prefix: bool = True
    # Chunked prefill: split shared-prefix prompt prefills into chunks of
    # this many tokens, interleaved with decode ticks. None = one-shot
    # prefill (the offline-equivalent setting).
    prefill_chunk_size: Optional[int] = None
    # Per-tick token budget shared by decode tokens (one per running
    # trace) and prefill tokens (chunks + one-shot prefills). None =
    # unlimited (admission bounded only by slots and blocks).
    max_tokens_per_step: Optional[int] = None
    # Cross-request prefix cache: park completed prompts' full KV blocks
    # in a radix tree and serve later requests' shared block-aligned
    # prefixes from it (COW fork, zero recompute); LRU-evicted before
    # any trace is pruned/preempted. Needs share_prompt_prefix and a
    # paged-attention arch (chunked prefill computes the suffix);
    # silently inactive otherwise. Default from REPRO_PREFIX_CACHE
    # (unset -> on).
    prefix_cache: bool = dataclasses.field(
        default_factory=_default_prefix_cache)
    # Decode horizon: run K decode iterations inside ONE jitted device
    # call (fused lax.scan with on-device sampling, EOS masking and
    # step-boundary score capture) and sync tokens/confidences/scores to
    # the host once per K tokens. 1 (default) reproduces the one-token-
    # per-tick scheduler exactly; K>1 amortizes the device->host round
    # trip and the Python tick overhead over K tokens, and generates
    # token-identical traces while scheduling stays aligned — i.e.
    # until memory contention shifts prune/preempt decisions, which
    # land at horizon granularity (greedy sampling is additionally
    # key-free, so it never depends on key-stream alignment — see
    # docs/ENGINE.md). Under admission pressure with a short free list
    # the engine falls back to a single-token tick so frontier
    # pre-allocation never starves waiting work.
    decode_horizon: int = 1
    # Deterministic fault injection: a FaultPlan spec string (see
    # serving/faults.py for the grammar, e.g. "step@2,alloc@5"), parsed
    # at engine construction and seeded with ``seed``. None = no
    # injection. Default from REPRO_FAULTS so the CI chaos lane can flip
    # whole test suites onto a fault plan without touching call sites.
    faults: Optional[str] = dataclasses.field(default_factory=_default_faults)
    # Paged-pool storage dtype: "f32" | "bf16" (default, the historical
    # pool dtype — pinned token/score/prune-identical to f32) | "int8" |
    # "fp8" (quantized: per-page per-KV-head f32 scales, quantize on
    # write, dequantize inside the attention read — dense and Pallas
    # paths apply identical math; see models/kv_quant.py and
    # docs/ENGINE.md "Quantized KV pool"). Quantized dtypes shrink
    # bytes-per-block ~4x/2x vs f32/bf16, so the same HBM sustains
    # proportionally more traces before the pruning policy fires.
    # Default from REPRO_KV_DTYPE (the CI test-kv-quant lane sets int8).
    kv_dtype: str = dataclasses.field(default_factory=_default_kv_dtype)

    # env var -> (field, parser, minimum); the single documented source
    # of truth for engine configuration from the environment
    # (REPRO_USE_KERNEL, REPRO_PREFIX_CACHE and REPRO_FAULTS
    # additionally act as dataclass defaults so the CI lanes flip whole
    # test suites without touching call sites).
    _ENV_FIELDS = {
        "REPRO_MAX_BATCH": ("max_batch", int, 1),
        "REPRO_NUM_BLOCKS": ("num_blocks", int, 2),
        "REPRO_CAPACITY": ("capacity", int, 1),
        "REPRO_MAX_NEW_TOKENS": ("max_new_tokens", int, 1),
        "REPRO_SEED": ("seed", int, 0),
        "REPRO_PREFILL_CHUNK": ("prefill_chunk_size", int, 1),
        "REPRO_MAX_TOKENS_PER_STEP": ("max_tokens_per_step", int, 1),
        "REPRO_DECODE_HORIZON": ("decode_horizon", int, 1),
    }

    @classmethod
    def from_env(cls, **overrides) -> "EngineConfig":
        """Build an ``EngineConfig`` from ``REPRO_*`` environment
        variables, with explicit keyword ``overrides`` taking
        precedence over the environment, which takes precedence over
        the dataclass defaults.

        Scalar fields read ``REPRO_MAX_BATCH``, ``REPRO_NUM_BLOCKS``,
        ``REPRO_CAPACITY``, ``REPRO_MAX_NEW_TOKENS``, ``REPRO_SEED``,
        ``REPRO_PREFILL_CHUNK``, ``REPRO_MAX_TOKENS_PER_STEP`` and
        ``REPRO_DECODE_HORIZON``; ``REPRO_USE_KERNEL`` /
        ``REPRO_PREFIX_CACHE`` / ``REPRO_FAULTS`` / ``REPRO_KV_DTYPE``
        keep their existing semantics (they are the dataclass default
        factories, so they apply to plain ``EngineConfig()``
        construction too). This is what
        ``launch/serve.py``, ``evaluate_method(_batched)`` and the
        benchmarks build their configs through — one documented source
        of truth instead of scattered ``os.environ`` reads.
        """
        kwargs = {}
        for env_name, (field, parse, lo) in cls._ENV_FIELDS.items():
            raw = os.environ.get(env_name, "").strip()
            if not raw:
                continue
            try:
                val = parse(raw)
            except ValueError:
                raise ValueError(
                    f"{env_name}={raw!r}: expected an integer >= {lo}"
                ) from None
            if val < lo:
                raise ValueError(
                    f"{env_name}={raw!r}: expected an integer >= {lo}")
            kwargs[field] = val
        kwargs.update(overrides)
        return cls(**kwargs)


@dataclasses.dataclass
class Request:
    """One unit of work for the scheduler: a prompt and a trace budget.

    ``arrival_time`` is in seconds relative to the start of the serve
    loop; the scheduler will not admit the request before it. 0.0 (the
    default) means available immediately, which reproduces the offline
    batch semantics.

    ``policy`` overrides the engine-level policy for this request; pass a
    fresh instance per request when the policy is stateful (DeepConf's
    warmup threshold, Slim-SC's check cursor) and requests run
    concurrently. When left None in a multi-request batch, the engine
    deep-copies its default policy per request for the same reason.

    Per-request generation overrides: ``sampling`` (a
    ``SamplingParams``) and ``max_new_tokens`` replace the engine-global
    ``EngineConfig.sampling`` / ``EngineConfig.max_new_tokens`` for this
    request only; ``None`` (the default) inherits the engine values, so
    existing callers are untouched. A batch where every request inherits
    the engine sampling runs the scalar decode path unchanged; any
    override flips that serve call onto the lane-wise sampling path
    (the same kept set per lane, up to the rounding of the top-p sum —
    see ``sampling.sample_logits_lanes``).

    Multi-tenant serving (consumed by ``scheduler.TenantScheduler``;
    inert under the default FIFO policy): ``tenant`` names the fair-share
    account the request's tokens are charged to, ``priority`` orders
    admission across tenants (higher first), and ``slo`` attaches a
    per-request ``scheduler.SLO`` — admission may degrade ``n_traces``
    toward ``slo.min_traces`` (test-time-scaling quality as the latency
    dial) or shed the request when its projected TTFT violates the
    objective.
    """
    request_id: int
    prompt_tokens: List[int]
    n_traces: int
    policy: Optional[PruningPolicy] = None
    arrival_time: float = 0.0
    sampling: Optional[SamplingParams] = None
    max_new_tokens: Optional[int] = None
    tenant: str = "default"
    priority: int = 0
    slo: Optional[SLO] = None
    # wall-clock budget in seconds relative to the serve start (same
    # clock as ``arrival_time``). Once exceeded the request is released
    # with status "deadline_exceeded"; traces already FINISHED keep
    # their output, so the vote runs over whatever completed in time.
    deadline: Optional[float] = None


@dataclasses.dataclass
class RequestResult:
    request_id: int
    answer: Optional[str]
    traces: List[Trace]
    latency_s: float
    total_tokens: int
    wait_s: float
    decode_s: float
    prefill_s: float
    num_pruned: int
    num_preemptions: int
    # pool-wide peak block usage observed up to this request's completion
    # (stable by the time the streaming on_complete callback sees it)
    peak_blocks_used: int = 0
    metrics: Optional[RequestMetrics] = None
    # "completed" | "cancelled" | "deadline_exceeded" | "failed"
    status: str = "completed"



class Engine:
    """Continuous-batching engine over a queue of requests, each fanning
    out into N parallel traces (the paper's setting: one problem, N=64
    traces — ``serve``; cross-request contention and online arrivals —
    ``serve_batch``).

    ``mesh`` (a ``("data", "model")`` jax mesh, e.g.
    ``launch.mesh.make_host_mesh(2, 2)``) runs the device-resident side
    over a device mesh: params tensor-parallel on "model"
    (``launch/shardings.serving_param_specs`` — the exactness-preserving
    layout whose only collectives are activation all-gathers), the
    paged KV pool head-sharded on "model" with its block dim replicated
    on "data" (``serving_cache_specs``), and the trace batch — tokens,
    positions, block tables, per-lane outputs, step scores — sharded on
    "data". Host-side scheduling (BlockManager, pruning, the queue) is
    untouched: the allocator stays global, and every scheduling decision
    consumes the same host-synced values, so a mesh engine is
    token-identical to the single-device engine under a fixed RNG
    (pinned in tests/test_sharded_engine.py)."""

    def __init__(self, params: dict, cfg: ModelConfig, ecfg: EngineConfig,
                 policy: PruningPolicy,
                 scorer_params: Optional[dict] = None,
                 mesh=None,
                 scheduler: Optional[SchedulingPolicy] = None):
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.policy = policy
        self.scorer_params = scorer_params
        self.mesh = mesh
        # scheduling policy (admission order, token budgets, SLO
        # admission, preemption victims). None -> REPRO_SCHED env
        # default (unset = the FIFO policy, which reproduces the
        # pre-scheduler-core tick loop exactly).
        self.scheduler = (scheduler if scheduler is not None
                          else default_scheduler())
        self.tok = get_tokenizer()
        bs = cfg.kv_block_size
        self.blocks_per_seq = -(-ecfg.capacity // bs)
        self._chunk_supported = supports_chunked_prefill(cfg)
        # pool storage dtype: validated against the arch up front so an
        # unsupported quantized setting fails at construction, and the
        # per-block HBM cost flows into the allocator's byte accounting
        # (AdmissionPressure reports real bytes, not just block counts)
        kv_quant.resolve_kv_dtype(ecfg.kv_dtype, cfg, self._chunk_supported)
        self.kv_block_bytes = kv_quant.pool_block_bytes(cfg, ecfg.kv_dtype)
        self.block_mgr = BlockManager(ecfg.num_blocks, bs,
                                      bytes_per_block=self.kv_block_bytes)
        self._rng = jax.random.PRNGKey(ecfg.seed)
        # cross-request prefix cache: needs the shared-prefix holder (the
        # parked blocks ARE a holder that outlives its request) and the
        # chunked-prefill path (the suffix continues from cached KV)
        self.prefix_cache: Optional[PrefixCache] = None
        if (ecfg.prefix_cache and ecfg.share_prompt_prefix
                and self._chunk_supported):
            self.prefix_cache = PrefixCache(self.block_mgr)
        # with the cache on, the device KV pool must outlive a single
        # serve_batch call — parked blocks are worthless if the pool
        # they point into is re-initialized (zeroed) between batches
        self._kv_cache = None
        # resolved kernel routing for the jitted steps (may raise for
        # unsupported explicit-True combinations — never wrong tokens)
        self.use_kernel = resolve_use_kernel(ecfg.use_kernel, cfg, mesh)
        assert ecfg.decode_horizon >= 1, "decode_horizon must be >= 1"
        # serve-loop counters (lanes, memory-full outcomes, work,
        # compiles), cumulative over the engine's life
        self.counters = ServeCounters()
        # fault tolerance: the injection plan (re-armed per serve so the
        # same perturbation replays), the recovery policy knobs, and the
        # cumulative ledger of injections/recoveries
        self.fault_plan: Optional[FaultPlan] = (
            FaultPlan.parse(ecfg.faults, seed=ecfg.seed)
            if ecfg.faults else None)
        self.recovery = RecoveryConfig()
        self.fault_stats = FaultStats()
        # persistent-fault degrade rung: pins every decode burst to
        # horizon 1 (token-identical by the K==1 equivalence pin)
        self.force_horizon1 = False
        # request ids flagged by Engine.cancel, consumed by the
        # scheduler core's cancellation sweep each pump iteration
        self._cancel_requests: set = set()
        self._ss = None  # serving step shardings (mesh engines only)
        if mesh is not None:
            self._place_on_mesh()
        self._build_steps()

    def _place_on_mesh(self) -> None:
        """Shard params/scorer onto the mesh and build the NamedSharding
        bundle the jitted steps pin their in/out layouts to."""
        from repro.launch.shardings import (serving_param_specs,
                                            serving_prefill_kv_specs,
                                            serving_step_shardings,
                                            to_named)
        mesh = self.mesh
        for axis in ("data", "model"):
            if axis not in mesh.axis_names:
                raise ValueError(f"serving mesh needs a {axis!r} axis, "
                                 f"got {mesh.axis_names}")
        data_n = mesh.shape["data"]
        if self.ecfg.max_batch % data_n != 0:
            raise ValueError(
                f"max_batch={self.ecfg.max_batch} must be a multiple of "
                f"the mesh's data axis ({data_n}) so decode lanes shard "
                f"evenly")
        if self.cfg.arch_type in ("ssm", "hybrid") \
                or self.cfg.is_encoder_decoder:
            raise NotImplementedError(
                "mesh serving covers the dense paged-attention archs; "
                "recurrent/enc-dec state would need a data-sharded "
                "slot-state story first")
        if self.cfg.use_mla or self.cfg.uses_moe:
            # the bit-identity contract requires every sharded matmul's
            # contractions to stay shard-local; MLA's low-rank norms
            # (rms over a model-sharded lora dim) and the MoE
            # router/dispatch reductions are not constrained yet
            raise NotImplementedError(
                "mesh serving's exactness layout does not cover "
                "MLA/MoE yet; run these archs on a single device")
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        pspecs = serving_param_specs(self.cfg, mesh, shapes)
        self.params = jax.device_put(self.params, to_named(mesh, pspecs))
        self._ss = serving_step_shardings(self.cfg, mesh,
                                          self.ecfg.kv_dtype)
        self._prefill_kv_specs = serving_prefill_kv_specs(self.cfg, mesh)
        if self.scorer_params is not None:
            # the scorer is a tiny MLP: replicate it so step-score
            # capture is a shard-local matmul over the data-sharded
            # hidden states (no gather per scored token)
            self.scorer_params = jax.device_put(self.scorer_params,
                                                self._ss["replicated"])

    # ------------------------------------------------------------------
    # jitted steps
    # ------------------------------------------------------------------
    def _build_steps(self):
        cfg, ecfg = self.cfg, self.ecfg
        has_scorer = self.scorer_params is not None
        sp = ecfg.sampling
        ss = self._ss  # NamedSharding bundle (None on a 1-device engine)
        step_kw = _step_jit_kw()

        V = cfg.vocab_size  # mask vocab padding out of the sampler
        eos_id = self.tok.eos_id
        # Fused step scorer: on the kernel path the scorer MLP runs as
        # the Pallas step_score kernel inside the decode burst, so the
        # [B, D] step-boundary hiddens feed the two matmuls from VMEM
        # instead of round-tripping through a separate dense pass. The
        # kernel computes the exact scorer_score graph (f32 matmuls,
        # ReLU, sigmoid) — pinned score-identical in tests. Mesh engines
        # keep the dense scorer: it is a shard-local matmul over the
        # data-sharded hiddens, and a pallas_call under GSPMD would need
        # its own shard_map plumbing for zero benefit at [B, D] sizes.
        self.fused_scorer = bool(has_scorer and self.use_kernel
                                 and ss is None)
        step_id = self.tok.step_id

        def mask_and_gather(logits):
            logits = logits.at[:, V:].set(-jnp.inf)
            if ss is not None:
                # The sampling math must never shard the vocab axis: the
                # top-p cumsum and softmax denominators are float
                # reductions whose cross-shard psum rounds differently
                # than the single-device sum and flips boundary samples.
                # Gathering the [B, Vp] logits (a few KB at decode
                # widths) and sampling replicated reproduces the
                # single-device sampler bit-for-bit.
                logits = jax.lax.with_sharding_constraint(
                    logits, ss["replicated"])
            return logits

        def sample_fn(key, logits):
            logits = mask_and_gather(logits)
            return sample_logits(key, logits, temperature=sp.temperature,
                                 top_k=sp.top_k, top_p=sp.top_p)

        def make_decode(horizon, lanewise=False):
            """Fused K-iteration decode; one jit instance per (horizon,
            lanewise). The lane-wise variant takes per-lane
            temperature/top-k/top-p arrays as traced arguments (the
            per-request sampling path); the scalar variant bakes the
            engine-global ``SamplingParams`` into the graph and is the
            only one built for batches with no overrides."""
            jit_kw = dict(step_kw)
            if ss is not None:
                # pin the round-trip layouts: per-lane [B, K] bursts and
                # next-tick state stay data-sharded, pools keep the
                # serving cache layout (donation then reuses the input
                # pool buffers), the key stays replicated
                t, lane = ss["table"], ss["lane"]
                jit_kw["out_shardings"] = (t, t, t, t, t, lane, lane,
                                           ss["pools"], ss["replicated"])

            @partial(jax.jit, donate_argnums=(1,), **jit_kw)
            def batched_decode(params, cache, tokens, positions, limits,
                               block_tables, rng, scorer_params, *samp):
                cache = dict(cache)
                cache["block_tables"] = block_tables
                if not has_scorer:
                    score_fn = None
                elif self.fused_scorer:
                    score_fn = (lambda h:
                                kops.step_score_params(h, scorer_params))
                else:
                    score_fn = (lambda h: scorer_score(scorer_params, h))
                if lanewise:
                    temps, topks, topps = samp

                    def lane_sample_fn(key, logits):
                        logits = mask_and_gather(logits)
                        return sample_logits_lanes(key, logits, temps,
                                                   topks, topps)

                    step_sample_fn = lane_sample_fn
                else:
                    step_sample_fn = sample_fn
                # derive the per-iteration keys in-graph, exactly as K
                # successive host-side ticks would (rng, k = split(rng)
                # per token) — one device call replaces K split
                # dispatches + a stack per tick
                keys = []
                for _ in range(horizon):
                    rng, k = jax.random.split(rng)
                    keys.append(k)
                out = multi_decode_step(
                    params, cfg, tokens, positions, limits, cache,
                    window_len=ecfg.capacity, horizon=horizon,
                    rng_keys=jnp.stack(keys), sample_fn=step_sample_fn,
                    eos_id=eos_id, step_id=step_id, score_fn=score_fn,
                    scratch_block=self.block_mgr.scratch_block,
                    use_kernel=self.use_kernel, shard_specs=ss)
                pools = out["cache"]
                pools.pop("block_tables", None)
                outs = (out["tokens"], out["confidences"], out["scores"],
                        out["token_valid"], out["score_valid"],
                        out["final_tokens"], out["positions"], pools, rng)
                if cfg.uses_moe:   # routed pairs per held expert
                    outs = outs + (out["expert_load"],)
                return outs

            return batched_decode

        self._make_decode = make_decode
        self._decode_fns: Dict[Tuple[int, bool], Callable] = {}
        self._decode_fns[(ecfg.decode_horizon, False)] = make_decode(
            ecfg.decode_horizon)
        # pressure-fallback path: single-token ticks while waiting work
        # contends for a short free list (same instance when K == 1)
        if (1, False) not in self._decode_fns:
            self._decode_fns[(1, False)] = make_decode(1)

        pf_kv = None if ss is None else self._prefill_kv_specs
        pf_act = None if ss is None else ss["prefill_act"]

        @partial(jax.jit, **step_kw)
        def prefill(params, tokens):
            out = forward_full(params, cfg, tokens, return_kv=True,
                               kv_specs=pf_kv, act_spec=pf_act,
                               tp_act_spec=pf_act)
            logits = out["logits"].at[..., V:].set(-jnp.inf)
            if ss is not None:
                # first-token sampling consumes these host-side: gather
                # off the vocab sharding so the sampler's top-p cumsum
                # never reduces over a sharded axis (see sample_fn)
                logits = jax.lax.with_sharding_constraint(
                    logits, ss["prefill_act"])
            return logits, out["kvs"]

        self._prefill = prefill

        # prompt-KV scatter into the paged pools (one-shot prefix path).
        # Jitted so a mesh engine can pin the output pools back to the
        # canonical cache layout right at the write.
        pool_keys = ("kv_pool",) if cfg.use_mla else ("k_pool", "v_pool")
        if kv_quant.is_quantized(ecfg.kv_dtype):
            pool_keys += ("k_scale", "v_scale")
        wkv_kw = dict(step_kw)
        if ss is not None:
            wkv_kw["out_shardings"] = {
                **{k: ss["pools"][k] for k in pool_keys},
                "block_tables": ss["replicated"],  # one batch-1 row
            }

        @partial(jax.jit, donate_argnums=(0,), **wkv_kw)
        def write_kv(sub_cache, kvs, lens):
            return write_prefill_kv(cfg, sub_cache, kvs, lens)

        self._write_kv = write_kv

        if self._chunk_supported:
            cp_kw = dict(step_kw)
            if ss is not None:
                # chunk jobs run one prompt at a time (batch 1): the
                # logits can't batch-shard, but the pools must come out
                # in the serving layout the decode step expects
                chunk_keys = ("k_pool", "v_pool")
                if kv_quant.is_quantized(ecfg.kv_dtype):
                    chunk_keys += ("k_scale", "v_scale")
                cp_kw["out_shardings"] = (
                    ss["replicated"],
                    {k: ss["pools"][k] for k in chunk_keys})

            @partial(jax.jit, donate_argnums=(1,), **cp_kw)
            def chunk_prefill(params, cache, tokens, positions, valid,
                              block_tables):
                cache = dict(cache)
                cache["block_tables"] = block_tables
                out = prefill_chunk_step(params, cfg, tokens, positions,
                                         valid, cache,
                                         window_len=ecfg.capacity,
                                         use_kernel=self.use_kernel,
                                         shard_specs=ss)
                logits = out["logits"].at[..., V:].set(-jnp.inf)
                new_cache = out["cache"]
                new_cache.pop("block_tables", None)
                return logits, new_cache

            self._chunk_prefill = chunk_prefill

        # COW block copy: pool[:, dst] = pool[:, src], one jitted instance
        # for all block pairs (src/dst are traced scalars).
        cb_kw = dict(step_kw)
        if ss is not None:
            cb_kw["out_shardings"] = ss["pools"]
        self._copy_block = jax.jit(partial(copy_kv_block, cfg),
                                   donate_argnums=(0,), **cb_kw)

    def decode_fn(self, horizon: int, lanewise: bool = False) -> Callable:
        """The fused decode step for ``(horizon, lanewise)``. Scalar
        instances for the configured horizon (and its K=1 pressure
        fallback) are built at construction; lane-wise instances (the
        per-request sampling path) compile lazily on the first serve
        call whose batch carries a sampling override."""
        key = (horizon, lanewise)
        fn = self._decode_fns.get(key)
        if fn is None:
            fn = self._decode_fns[key] = self._make_decode(horizon,
                                                           lanewise)
        return fn

    # ------------------------------------------------------------------
    # host-side sampling (prefill first tokens)
    # ------------------------------------------------------------------
    def sample_host(self, logits, sp: SamplingParams):
        """Sample one token per row of ``logits`` with scalar params,
        consuming one split of the engine RNG stream (exactly what the
        pre-refactor tick loop did — the identity pins depend on this
        key-consumption order)."""
        self._rng, k = jax.random.split(self._rng)
        self.counters.note_sampled(
            sampler_path(sp, logits.shape[-1], lanewise=False),
            logits.shape[0])
        return sample_tokens(k, logits, temperature=sp.temperature,
                             top_k=sp.top_k, top_p=sp.top_p)

    def sample_host_lanes(self, logits, sps: Sequence[SamplingParams]):
        """Per-row sampling params (mixed-sampling admission waves);
        same single RNG split as ``sample_host``."""
        self._rng, k = jax.random.split(self._rng)
        temps = jnp.asarray([s.temperature for s in sps], jnp.float32)
        topks = jnp.asarray([s.top_k for s in sps], jnp.int32)
        topps = jnp.asarray([s.top_p for s in sps], jnp.float32)
        self.counters.note_sampled("sort", logits.shape[0])
        return sample_tokens_lanes(k, logits, temps, topks, topps)

    # ------------------------------------------------------------------
    # KV pool handoff (scheduler core <-> persistent prefix-cache pool)
    # ------------------------------------------------------------------
    def _take_kv_cache(self) -> dict:
        """Hand the device KV pool to a scheduler run. With the prefix
        cache on, the pool persists across serve calls (parked blocks
        keep their KV); ownership transfers because the first jitted
        step donates the buffers, so no second reference may survive."""
        if self.prefix_cache is not None and self._kv_cache is not None:
            cache, self._kv_cache = self._kv_cache, None
            return cache
        return self._init_cache()

    def _stash_kv_cache(self, cache: dict) -> None:
        """Keep parked KV live for the next serve call (cache on)."""
        if self.prefix_cache is not None:
            self._kv_cache = cache

    # ------------------------------------------------------------------
    # pool accounting
    # ------------------------------------------------------------------
    @property
    def idle_free_blocks(self) -> int:
        """Free-list blocks plus blocks parked in the prefix cache —
        everything reclaimable when no request is live."""
        cached = (self.prefix_cache.cached_blocks
                  if self.prefix_cache is not None else 0)
        return self.block_mgr.free_blocks + cached

    def pool_drained(self) -> bool:
        """True when no live request holds pool memory: every non-free
        block is parked in the prefix cache at refcount exactly 1 (the
        cache's own reference). With the cache off this degenerates to
        ``free_blocks == num_blocks - 1`` — the pre-cache drain check."""
        if self.prefix_cache is not None:
            self.prefix_cache.check_integrity()
            if any(self.block_mgr.ref_count(b) != 1
                   for b in self.prefix_cache.blocks()):
                return False
        return self.idle_free_blocks == self.block_mgr.num_blocks - 1

    # ------------------------------------------------------------------
    # fault tolerance
    # ------------------------------------------------------------------
    def cancel(self, request_id: int) -> None:
        """Flag a request for mid-flight cancellation. Safe to call from
        an ``on_complete`` callback (or any code running inside the
        serve loop): the scheduler core's sweep releases the request's
        traces, reservations and prefix-cache refs at the next pump
        iteration and stamps its result ``status="cancelled"``. Unknown
        or already-finished ids are ignored."""
        self._cancel_requests.add(request_id)

    def degrade_to_dense(self) -> bool:
        """Persistent-fault ladder rung: drop the Pallas kernel path and
        rebuild the jitted steps on dense XLA. Token-identical — the
        kernel/dense equivalence is pinned by the kernel CI lane.
        Returns False when already dense (rung unavailable)."""
        if not self.use_kernel:
            return False
        self.use_kernel = False
        self._build_steps()
        self.fault_stats.degraded_to_dense += 1
        return True

    def check_integrity(self, expect_open_reservations: int = 0) -> None:
        """Pool-wide invariant audit: allocator refcount conservation,
        no orphaned reservations, prefix-trie consistency. Cheap enough
        that the scheduler core runs it after every fault/cancel path;
        tests call it at any quiesced point."""
        self.block_mgr.check_integrity(expect_open_reservations)
        if self.prefix_cache is not None:
            self.prefix_cache.check_integrity()
        self.fault_stats.integrity_audits += 1

    # ------------------------------------------------------------------
    # cache plumbing
    # ------------------------------------------------------------------
    def _init_cache(self):
        """Shared pool sized to the engine budget (not per-sequence)."""
        cache = init_decode_cache(
            self.cfg, self.ecfg.max_batch, self.ecfg.capacity,
            num_blocks=self.ecfg.num_blocks,
            kv_dtype=self.ecfg.kv_dtype)
        cache.pop("block_tables", None)
        if self._ss is not None:
            cache = {k: jax.device_put(v, self._ss["pools"][k])
                     for k, v in cache.items()}
        return cache

    def _split_prefill_kvs(self, kvs) -> Tuple[Optional[tuple],
                                               Optional[tuple]]:
        """Split forward_full(return_kv=True) output for a batch-1 prefill
        into (paged attention KV | None, per-slot recurrent state | None).
        """
        cfg = self.cfg
        if cfg.arch_type == "ssm":
            ss, cs = kvs
            return None, (ss[:, 0], cs[:, 0])
        if cfg.arch_type == "hybrid":
            (ss, cs), (k, v) = kvs
            ssf = ss.reshape(-1, *ss.shape[2:])
            csf = cs.reshape(-1, *cs.shape[2:])
            return (k[:, :1], v[:, :1]), (ssf[:, 0], csf[:, 0])
        if cfg.use_mla:
            return kvs[:, :1], None
        k, v = kvs
        return (k[:, :1], v[:, :1]), None

    def _write_prefix_kv(self, cache: dict, attn_kvs, block_row: np.ndarray,
                         seq_len: int) -> dict:
        """Write prompt KV into the paged pools ONCE for a block row.

        With prefix sharing this runs once per request; every trace then
        reads these blocks through its forked block table.
        """
        if attn_kvs is None:
            return cache
        cfg = self.cfg
        bt = jnp.asarray(block_row[None, :], jnp.int32)  # [1, bp]
        lens = jnp.full((1,), seq_len, jnp.int32)
        if cfg.use_mla:
            sub = {"kv_pool": cache["kv_pool"], "block_tables": bt}
            sub = self._write_kv(sub, attn_kvs, lens)
            cache["kv_pool"] = sub["kv_pool"]
            return cache
        k, v = attn_kvs
        sub = {"k_pool": cache["k_pool"], "v_pool": cache["v_pool"],
               "block_tables": bt}
        if "k_scale" in cache:  # quantized pools: scales ride along
            sub["k_scale"] = cache["k_scale"]
            sub["v_scale"] = cache["v_scale"]
        sub = self._write_kv(sub, (k, v), lens)
        cache["k_pool"], cache["v_pool"] = sub["k_pool"], sub["v_pool"]
        if "k_scale" in sub:
            cache["k_scale"] = sub["k_scale"]
            cache["v_scale"] = sub["v_scale"]
        return cache

    def _write_slot_state(self, cache: dict, slot_state, slot: int) -> dict:
        """Scatter recurrent (SSM/conv) prefill end-state into one slot."""
        if slot_state is None:
            return cache
        ss, cs = slot_state
        cache["ssm_state"] = cache["ssm_state"].at[:, slot].set(ss)
        cache["conv_state"] = cache["conv_state"].at[:, slot].set(cs)
        return cache

    def _write_prefill(self, cache: dict, kvs, slot: int,
                       block_row: np.ndarray, seq_len: int) -> dict:
        """Scatter one trace's prefill KV/state into the shared pool."""
        attn_kvs, slot_state = self._split_prefill_kvs(kvs)
        cache = self._write_prefix_kv(cache, attn_kvs, block_row, seq_len)
        return self._write_slot_state(cache, slot_state, slot)

    def _clear_slot_state(self, cache: dict, slot: int) -> dict:
        if "ssm_state" in cache:
            cache["ssm_state"] = cache["ssm_state"].at[:, slot].set(0.0)
            cache["conv_state"] = cache["conv_state"].at[:, slot].set(0.0)
        return cache

    # ------------------------------------------------------------------
    # request serving
    # ------------------------------------------------------------------
    def serve(self, prompt_tokens: List[int], n_traces: int,
              request_id: int = 0) -> RequestResult:
        """Generate ``n_traces`` parallel traces for one prompt."""
        assert n_traces <= self.ecfg.max_batch, "engine sized per trace budget"
        req = Request(request_id=request_id,
                      prompt_tokens=list(prompt_tokens),
                      n_traces=n_traces, policy=self.policy)
        return self.serve_batch([req])[0]

    def serve_batch(self, requests: Sequence[Request],
                    on_complete: Optional[Callable[[RequestResult], None]]
                    = None) -> List[RequestResult]:
        """Serve a queue of requests through one shared decode batch.

        Requests join the scheduler at their ``arrival_time``; total
        traces may exceed ``max_batch`` (surplus traces wait for a free
        decode slot). Block-pool contention is cross-request; each
        request's own policy governs pruning of its traces.

        ``on_complete`` streams results: it is invoked with a request's
        ``RequestResult`` the moment its last trace finishes, while other
        requests are still decoding. The returned list is in submission
        order, as before.
        """
        t_start = time.perf_counter()
        states: List[ReqState] = []
        for req in requests:
            if req.policy is not None:
                policy = req.policy
            elif len(requests) == 1:
                policy = self.policy
            else:
                # stateful policies (DeepConf threshold, Slim-SC cursors)
                # must not leak between concurrent requests: give each
                # request its own copy of the engine-level default
                policy = copy.deepcopy(self.policy)
            if isinstance(policy, DeepConfPolicy):
                policy.threshold = None  # fresh threshold per request
            traces = [Trace(trace_id=i, request_id=req.request_id,
                            prompt_tokens=list(req.prompt_tokens))
                      for i in range(req.n_traces)]
            states.append(ReqState(
                req, policy, traces,
                sampling=(req.sampling if req.sampling is not None
                          else self.ecfg.sampling),
                max_new_tokens=(req.max_new_tokens
                                if req.max_new_tokens is not None
                                else self.ecfg.max_new_tokens)))

        if self.fault_plan is not None:
            self.fault_plan.reset()  # replay the identical plan per serve
        with counting(self.counters):
            core = SchedulerCore(self, states, t_start, on_complete,
                                 sched=self.scheduler)
            try:
                peak_blocks = core.run()
            except BaseException:
                # mid-serve crash: drain everything the run still held
                # so the pool is clean and the engine reusable, re-raise
                core.emergency_drain()
                raise

        t_end = time.perf_counter()
        results = []
        for st in states:
            if st.result is None:  # defensive: finalize stragglers
                st.result = self._finalize(st, t_start, t_end, peak_blocks)
            results.append(st.result)
        return results

    def _finalize(self, st: _ReqState, t_start: float, t_end: float,
                  peak_blocks: int) -> RequestResult:
        """Fold one finished request's traces into its RequestResult."""
        finished = [t for t in st.traces if t.status == TraceStatus.FINISHED]
        answer = st.policy.vote(finished) if finished else None
        done = st.t_done if st.t_done is not None else t_end
        total_tokens = sum(t.num_tokens for t in st.traces)
        num_pruned = sum(t.status == TraceStatus.PRUNED for t in st.traces)
        num_failed = sum(t.status == TraceStatus.FAILED for t in st.traces)
        num_preempt = sum(max(t.prefill_count - 1, 0) for t in st.traces)
        wait_s = sum(t.wait_time for t in st.traces)
        metrics = RequestMetrics(
            request_id=st.request_id,
            arrival_s=st.req.arrival_time,
            admitted_s=(st.admit_t - t_start
                        if st.admit_t is not None else None),
            first_token_s=(st.first_token_t - t_start
                           if st.first_token_t is not None else None),
            finished_s=done - t_start,
            prompt_tokens=len(st.req.prompt_tokens),
            output_tokens=total_tokens,
            n_traces=len(st.traces),
            num_pruned=num_pruned,
            num_preemptions=num_preempt,
            wait_s=wait_s, prefill_s=st.prefill_s, decode_s=st.decode_s,
            cached_tokens=st.cached_tokens,
            tenant=getattr(st.req, "tenant", "default"),
            priority=getattr(st.req, "priority", 0),
            degraded_traces=st.degraded_traces,
            slo_ttft_s=(st.req.slo.ttft_s if st.req.slo is not None
                        else None),
            slo_tpot_s=(st.req.slo.tpot_s if st.req.slo is not None
                        else None),
            status=st.final_status,
            failed_traces=num_failed)
        return RequestResult(
            request_id=st.request_id, answer=answer, traces=st.traces,
            latency_s=done - t_start,
            total_tokens=total_tokens,
            wait_s=wait_s,
            decode_s=st.decode_s, prefill_s=st.prefill_s,
            num_pruned=num_pruned,
            num_preemptions=num_preempt,
            peak_blocks_used=peak_blocks,
            metrics=metrics,
            status=st.final_status)

