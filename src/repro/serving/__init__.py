from repro.serving.engine import (Engine, EngineConfig, Request,
                                  RequestResult, resolve_use_kernel)
from repro.serving.evaluate import (EvalResult, evaluate_method,
                                    evaluate_method_batched, make_problems,
                                    poisson_arrivals)
from repro.serving.faults import (DeviceStepFault, FatalFaultError,
                                  FaultPlan, FaultSpec, FaultStats,
                                  RecoveryConfig)
from repro.serving.kv_manager import BlockManager, Reservation
from repro.serving.metrics import (RequestMetrics, ServeCounters,
                                   percentiles, span, summarize,
                                   summarize_by_tenant)
from repro.serving.prefix_cache import CacheStats, PrefixCache
from repro.serving.queue import RequestQueue
from repro.serving.sampling import (SamplingParams, sample_tokens,
                                    sample_tokens_lanes)
from repro.serving.scheduler import (SLO, Arrival, BudgetReplenish,
                                     BurstDone, Cancelled, ChunkDone,
                                     Completion,
                                     DeficitRoundRobin, Event, FIFOPolicy,
                                     SchedulerCore, SchedulingPolicy,
                                     TenantScheduler, TokenBudget,
                                     WeightedTokenBudget, default_scheduler,
                                     parse_tenant_weights)

__all__ = [
    "Engine", "EngineConfig", "Request", "RequestResult",
    "resolve_use_kernel",
    "EvalResult", "evaluate_method", "evaluate_method_batched",
    "make_problems", "poisson_arrivals",
    "BlockManager", "Reservation", "RequestQueue",
    "PrefixCache", "CacheStats",
    "RequestMetrics", "ServeCounters", "span", "percentiles", "summarize",
    "summarize_by_tenant",
    "SamplingParams", "sample_tokens", "sample_tokens_lanes",
    "SLO", "SchedulerCore", "SchedulingPolicy", "FIFOPolicy",
    "TenantScheduler", "DeficitRoundRobin", "TokenBudget",
    "WeightedTokenBudget", "default_scheduler", "parse_tenant_weights",
    "Event", "Arrival", "BudgetReplenish", "ChunkDone", "BurstDone",
    "Completion", "Cancelled",
    "FaultPlan", "FaultSpec", "FaultStats", "RecoveryConfig",
    "DeviceStepFault", "FatalFaultError",
]
