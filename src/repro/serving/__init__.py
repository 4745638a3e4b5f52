"""Serving: the request-level inference surface for the whole repo.

Canonical path:  deploy() -> TranslationPipeline -> SamplingParams /
Request / RequestOutput, scheduled by the queue-owning ServeEngine
(submit / step / run_until_drained / stream). Tokens stream as each
fused horizon block lands — `submit(..., on_token=cb)`,
`engine.stream_request(...)`, `pipe.translate_stream(...)` — and
`deploy(..., sla=SLATarget(...))` attaches percentile-feedback
admission control; `engine.metrics()` returns the one frozen
EngineMetrics snapshot every benchmark reads. Speculative decoding
deploys a second arm of the same checkpoint via
`deploy(..., draft_spec=...)` (see spec_decode).

Fault tolerance: requests carry `SamplingParams(deadline_ms=...,
priority=...)` and retire with a `finish_reason` from FINISH_REASONS;
`deploy(..., max_pending=N)` bounds admission (`submit` raises the
typed EngineSaturated under saturation); on-demand paged engines
preempt and transparently resume requests under page pressure; and
`deploy(..., faults=FaultPlan(...))` injects deterministic allocator
exhaustion / NaN logits / clock skew for chaos testing.

Observability: `deploy(..., trace=TraceConfig())` wires an `obs.Tracer`
into the engine — per-request lifecycle spans and scheduler round-phase
timing, exportable as Chrome/Perfetto JSON (`pipe.tracer.dump_json`);
`engine.prometheus()` renders the metrics snapshot + ttft/tpot/phase
histograms as Prometheus text (see `repro.obs`).

Scale-out: `deploy(..., mesh=...)` tensor-shards one engine over a
`("model",)` device mesh; `repro.cluster` adds the data-parallel
`ReplicaRouter` / `deploy_replicas` layer on top, aggregating replica
snapshots with `merge_metrics`.

`greedy_generate` / `translate` remain as deprecated single-shot
wrappers for legacy callers.
"""

from ..obs import TraceConfig, Tracer
from .engine import ServeEngine, greedy_generate, translate
from .faults import FaultPlan
from .metrics import EngineMetrics, SLATarget, merge_metrics
from .paged_cache import PageAllocator, pages_needed
from .params import (FINISH_REASONS, GREEDY, EngineSaturated, Request,
                     RequestOutput, RequestStats, RoundBudgetExhausted,
                     SamplingParams, latency_percentiles)
from .pipeline import IMPL_CHOICES, TranslationPipeline, deploy, impl_routes
from .sampler import ERR_TOKEN
from .spec_decode import DraftArm, accept_longest_prefix, build_draft_arm

__all__ = ["ServeEngine", "greedy_generate", "translate", "SamplingParams",
           "GREEDY", "Request", "RequestOutput", "RequestStats",
           "latency_percentiles", "TranslationPipeline", "deploy",
           "PageAllocator", "pages_needed", "impl_routes", "IMPL_CHOICES",
           "DraftArm", "accept_longest_prefix", "build_draft_arm",
           "EngineMetrics", "SLATarget", "merge_metrics", "EngineSaturated",
           "RoundBudgetExhausted", "FaultPlan",
           "FINISH_REASONS", "ERR_TOKEN", "TraceConfig", "Tracer"]
