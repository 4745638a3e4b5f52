"""Request-level serving types: SamplingParams / Request / RequestOutput.

These are the load-bearing abstraction of the serving stack (the vLLM
convention adapted to the paper's quantized-NMT deployment): every
inference call in the repo is a `Request` carrying its own frozen
`SamplingParams`, and every completion is a `RequestOutput` with an
explicit finish reason and timing stats. Finish reasons cover the
fault-tolerant paths too — a request always comes back with a typed
outcome instead of an exception escaping the serving loop:

  * ``eos`` / ``length``    — normal completion.
  * ``abort``               — cancelled by the caller.
  * ``deadline``            — ``deadline_ms`` elapsed before completion
    (partial tokens are returned).
  * ``preempted_limit``     — preempted for pages more than the
    engine's ``preempt_limit`` times; retired with partial tokens
    rather than thrashing the pool forever.
  * ``error``               — the model produced non-finite logits for
    this request (sampler NaN/Inf guard); only the offending slot
    fails, with its partial tokens, while the fused batch continues.

``EngineSaturated`` is the typed admission rejection raised by
``submit`` when the engine's bounded pending queue (``max_pending``) is
full — callers retry with backoff instead of seeing an allocator error
from deep inside the engine.

Sampling semantics:
  * ``temperature == 0.0``  -> greedy argmax (the default).
  * ``temperature > 0``     -> softmax sampling at that temperature,
    optionally restricted by ``top_k`` (0 = off) and/or nucleus
    ``top_p`` (1.0 = off), drawn from a per-request PRNG stream seeded
    by ``seed`` — same seed, same tokens, regardless of which slot or
    batch the request lands in.
  * ``eos_id``              -> generation stops the step this token is
    emitted (it is included in the output); ``None`` disables EOS
    stopping (token 0 is the pad id in the synthetic corpora, so there
    is deliberately no implicit default).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["SamplingParams", "GREEDY", "Request", "RequestOutput",
           "RequestStats", "FINISH_REASONS", "EngineSaturated",
           "RoundBudgetExhausted", "latency_percentiles"]

FINISH_REASONS = ("eos", "length", "abort", "deadline", "preempted_limit",
                  "error")


class EngineSaturated(RuntimeError):
    """Typed backpressure signal: the engine's bounded pending queue is
    full. Carries ``pending`` (queue depth at rejection) and ``limit``
    (the engine's ``max_pending``) so callers can implement
    retry-with-backoff without parsing the message."""

    def __init__(self, pending: int, limit: int):
        self.pending = pending
        self.limit = limit
        super().__init__(
            f"engine saturated: {pending} requests pending >= "
            f"max_pending={limit}; retry after draining (engine.step() / "
            f"stream()) or deploy with a larger max_pending")


class RoundBudgetExhausted(RuntimeError):
    """The round loop ran ``max_rounds`` rounds without draining. Typed
    so that a caller that ends quietly on it (``stream_request``) lets
    every other error, a device fault above all, propagate."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy. Frozen: shareable across requests."""

    temperature: float = 0.0      # 0.0 = greedy
    top_k: int = 0                # 0 = disabled
    top_p: float = 1.0            # 1.0 = disabled
    eos_id: Optional[int] = None  # None = never stop on a token id
    max_new_tokens: int = 16      # includes the prefill-sampled first token
    seed: int = 0                 # per-request PRNG stream seed
    deadline_ms: Optional[float] = None  # wall-clock budget from submit;
    #                               checked at horizon boundaries (None = no
    #                               deadline); an expired request retires
    #                               with finish_reason "deadline" and
    #                               whatever tokens it has
    priority: int = 0             # preemption victim ordering: on page-pool
    #                               exhaustion the lowest-priority (then
    #                               youngest) request is evicted first

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be positive, got {self.deadline_ms}")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


GREEDY = SamplingParams()


@dataclasses.dataclass
class Request:
    """One inference request: a B=1 model batch dict + sampling params.

    ``inputs`` follows the ModelAPI batch convention — ``{"tokens"}`` for
    LM families, ``{"src_tokens", "tgt_in"}`` for enc-dec. ``id`` is
    assigned by the engine at submit time.

    ``on_token`` is the streaming hook: the engine calls it with each
    token id as the horizon block carrying that token lands on the host
    (the prefill-sampled first token fires at admission). Callbacks run
    on the scheduler's walk of the synced block — keep them cheap, and
    note that aborting the request from inside its own callback wins
    over an EOS in the same block (finish reason becomes ``abort``).
    """

    inputs: Dict[str, Any]
    params: SamplingParams = GREEDY
    id: Optional[int] = None
    on_token: Optional[Callable[[int], None]] = None


@dataclasses.dataclass
class RequestStats:
    """Wall-clock stamps (time.perf_counter) + derived serving metrics.

    ``new_tokens`` is the count of tokens actually delivered to the
    caller — under horizon-fused decode an aborted request is truncated
    at its last *synced* position, so this is the authoritative count
    (always equal to ``len(RequestOutput.token_ids)``), not the number
    of device-side decode steps the slot participated in.

    ``drafted`` / ``accepted`` / ``rejected`` count speculative-decode
    draft tokens proposed for this request, how many the target model's
    verify pass accepted, and how many it threw away (all zero on a
    target-only engine). ``accepted + rejected == drafted`` for every
    completed verify round the request participated in.

    ``preemptions`` counts how many times the request was evicted from
    its slot for page pressure and later resumed via prefill-replay —
    the token stream is unaffected (resume is provably identical), only
    latency pays.
    """

    arrival_s: float = 0.0
    first_token_s: float = 0.0
    finished_s: float = 0.0
    prompt_len: int = 0
    new_tokens: int = 0
    drafted: int = 0
    accepted: int = 0
    rejected: int = 0
    preemptions: int = 0

    @property
    def ttft_s(self) -> float:
        return self.first_token_s - self.arrival_s

    @property
    def total_s(self) -> float:
        return self.finished_s - self.arrival_s


@dataclasses.dataclass
class RequestOutput:
    """Completion record for one request."""

    request_id: int
    prompt: Dict[str, Any]
    token_ids: List[int]
    finish_reason: str            # one of FINISH_REASONS
    stats: RequestStats
    slot: int = -1                # engine slot that served the request

    @property
    def num_generated(self) -> int:
        return len(self.token_ids)

    @property
    def tok_s(self) -> float:
        dt = self.stats.total_s
        return self.num_generated / dt if dt > 0 else float("inf")

    @property
    def ttft_ms(self) -> float:
        """Time to first token (ms): submit -> prefill token delivered."""
        return self.stats.ttft_s * 1e3

    @property
    def tpot_ms(self) -> float:
        """Per-output-token latency (ms) after the first token.

        The post-first-token span over the decode steps the request took
        (``new_tokens - 1``; a one-token request contributes its whole
        span). Same definition ``latency_percentiles`` aggregates, so a
        single streamed request and a benchmark row read the same way.
        """
        return ((self.stats.total_s - self.stats.ttft_s)
                / max(self.num_generated - 1, 1)) * 1e3


def latency_percentiles(outputs: Sequence["RequestOutput"]) -> Dict[str, float]:
    """p50/p95 TTFT and per-output-token latency (ms) over completions.

    The shared serving-latency summary: benchmarks/bench_serving.py
    records it per BENCH row and repro.eval.suite per language pair, so
    quality and perf artifacts carry identically-defined columns.
    Per-output-token time divides the post-first-token span by the
    number of decode steps the request took (``new_tokens - 1``; a
    one-token request contributes its whole span).

    Percentiles are the repo-wide nearest-rank definition
    (``obs.metrics.percentile`` — also what the SLA controller and
    ``EngineMetrics``' histogram fields use), so the same sample can
    never read as "held" in one surface and "violated" in another.
    """
    from ..obs.metrics import percentile

    ttft = [o.ttft_ms for o in outputs]
    tpot = [o.tpot_ms for o in outputs]
    return {"ttft_p50_ms": round(percentile(ttft, 50), 3),
            "ttft_p95_ms": round(percentile(ttft, 95), 3),
            "tpot_p50_ms": round(percentile(tpot, 50), 3),
            "tpot_p95_ms": round(percentile(tpot, 95), 3)}
