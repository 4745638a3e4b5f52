"""Scheduler-owned serving engine: request-level continuous batching
with horizon-fused decode.

The paper's deployment is real-time quantized translation; the TPU
counterpart is a fixed-slot continuous-batching decode loop over a
(possibly int8-quantized) KV cache. This module owns the whole serving
loop — admission queue, slot scheduling, prefill, fused sampling, and
EOS-aware retirement — behind three calls:

    rid  = engine.submit(inputs, SamplingParams(...))   # enqueue
    outs = engine.step()       # admit + one fused decode horizon
    outs = engine.run_until_drained()                   # serve everything

Streaming + the overlapped scheduler
------------------------------------
``submit(..., on_token=cb)`` registers a per-request streaming callback:
the engine fires it with each token id as the horizon block carrying
that token lands on the host (first token at prefill). ``stream()``
yields RequestOutputs as requests finish; ``stream_request()`` submits
one request and yields its tokens as they arrive.

Internally everything drains through ONE loop, ``_rounds()``: a
double-buffered step generator that dispatches horizon N+1 *before*
syncing horizon N's token block, using the scan's own final alive/rem
carry as the next scan's masks. JAX async dispatch makes this the whole
trick — the host walk of block N (retire/stream/admit, all Python) runs
while the device is already busy with N+1, and freed slots refill from
the prompt queue between dispatches instead of waiting for a drain
point. The in-scan retirement rule (EOS + budget) is exactly the rule
the host walk applies, so the device carry always equals the host's
post-walk view for continuing slots; slots admitted or aborted between
dispatches are merged in from host state (``_dirty_slots``). Token
streams are token-for-token identical to serial stepping at any horizon
— slots never attend to each other, so overlap moves *when* work
happens, never *what* is computed. ``run_until_drained`` is a thin
wrapper over this loop; ``overlap=False`` (or ``horizon=1``, or a draft
arm, whose speculative rounds are host decision points) degrades it to
the serial dispatch-then-walk order.

With ``sla=SLATarget(...)`` an ``SLAController`` folds every retired
request's TTFT/TPOT into a sliding window and retunes the effective
horizon and the paged prefill-group cap against the measured p95s (see
serving/metrics.py).

The horizon knob
----------------
``step(horizon=K)`` (default: the engine's ``horizon``, default 1) runs
``K`` decode+sample steps inside ONE jitted ``lax.scan`` and reads the
resulting ``(K, slots)`` token block back to the host ONCE, instead of
dispatching one jitted step and syncing one token at a time. The scan
threads the KV cache, per-slot current tokens, PRNG offsets, remaining
token budgets, and an alive-mask; a slot that emits ``eos_id`` or
exhausts ``max_new_tokens`` mid-horizon keeps decoding into masked
positions (its ``len`` freezes, it emits pad) until the horizon ends.
Paged caches are scan-safe because block tables are static across the
horizon: chains either hold the full budget at admission (draft-armed
engines) or are grown to cover the scan just before each dispatch
(on-demand engines — see _grow_chains).

What the knob trades: per-token host overhead (Python dispatch + one
device->host transfer per generated token) against admission latency —
retirement, page reclaim, and queue admission happen only at horizon
boundaries, so a freed slot can idle for up to ``K - 1`` micro-steps.
``horizon=1`` routes through the original per-token step and is
guaranteed token-for-token identical to previous releases (dense and
paged); ``horizon=K`` produces identical per-request token streams,
finish reasons, and stats — only the sync granularity changes.
``engine.decode_syncs`` / ``engine.mean_tokens_per_sync`` report how
much host traffic the fusion eliminated.

Fault tolerance
---------------
Every failure mode resolves to a typed RequestOutput finish reason —
nothing raises out of ``step()``/``stream()`` once a request is
admitted (see serving/params.py for the reason vocabulary):

  * **Deadlines** — ``SamplingParams.deadline_ms`` is checked at every
    round boundary against a host-side clock (no extra device sync);
    expired requests retire as ``deadline`` with their partial tokens
    and free their pages immediately, queued or in-flight.
  * **Backpressure** — ``max_pending`` bounds the admission queue;
    ``submit`` raises the typed ``EngineSaturated`` instead of letting
    an overload surface as an allocator error deep in a later step.
  * **On-demand paging + preemption** — paged target-only engines
    allocate prefill pages at admission and grow each chain just ahead
    of every dispatched horizon (``on_demand``); on pool exhaustion
    the lowest-priority / youngest request is preempted — tokens
    stashed host-side, chain freed, request requeued at the head — and
    later resumed by prefill-replay (teacher-forced prefill is
    bit-exact vs incremental decode and the PRNG stream is
    offset-indexed, so resumed streams are token-identical to an
    uncontended run). ``preempt_limit`` consecutive evictions retire
    the request as ``preempted_limit``. Draft-armed engines keep the
    whole-budget reservation (two rollback-symmetric chains per
    request make mid-decode growth a poor trade).
  * **Poisoned requests** — non-finite logits sample the ERR_TOKEN
    sentinel (see sampler.py); the host walk retires only that slot as
    ``error`` while the fused batch keeps decoding.
  * **Fault injection** — ``faults=FaultPlan(...)`` (serving/faults.py)
    deterministically injects pool exhaustion, NaN logits, and clock
    skew at chosen rounds/dispatches; counters land in EngineMetrics
    (``preemptions``, ``deadline_expirations``, ...).

Speculative decoding (``draft=DraftArm(...)``)
----------------------------------------------
With a draft arm (see spec_decode.py: the SAME checkpoint quantized at
an aggressive spec), every step whose active slots are all greedy runs
a *speculative round* instead: the draft arm proposes
``draft.lookahead`` tokens via the horizon scan, the target arm replays
them in ONE batched teacher-forced forward, and the longest matching
prefix (+ the target's token at the first divergence) is emitted —
1..K tokens per slot per round, token-for-token identical to
target-only greedy decoding. Any sampled request in the batch falls the
step back to the target-only path. Both arms keep per-slot caches
(paged engines: two chains per request out of ONE shared allocator,
freed together at retirement); a rejection rolls BOTH caches back to
the emitted length. ``acceptance_rate`` / ``mean_accepted_per_verify``
/ ``verify_calls`` report how much draft work converted into output.

Design notes:
  * One jitted fused decode+sample step (or K-step scan) serves every
    slot each tick; per-slot SamplingParams enter as traced arrays, so
    greedy and nucleus-sampled requests share a single executable per
    horizon length (see sampler.py).
  * Single-request prefills are padded to a small set of bucket lengths
    (powers of two up to ``max_len``) with per-sequence ``lengths``
    masking, so distinct prompt lengths stop triggering fresh XLA
    compiles; ``engine.prefill_compiles`` counts distinct compiled
    prefill shapes. (SSM/hybrid state caches have no position masking,
    so those families prefill at exact lengths.)
  * Slots retire as soon as the host sees ``eos_id`` or the
    ``max_new_tokens``-th token in the synced block; idle slots decode
    into masked positions (their ``len`` stays put) at negligible cost
    relative to the batched step.

``greedy_generate`` / ``translate`` remain as thin wrappers over a
single-shot engine so pre-request-API callers stay green.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.api import decode_block
from ..models.layers import Ctx
from ..obs import (ADMIT_TID, PHASES, SCHED_TID, Histogram, TraceConfig,
                   Tracer)
from ..parallel import (cache_shardings, paged_pool_shardings,
                        param_shardings, set_mesh)
from ..obs.metrics import render_prometheus
from .metrics import EngineMetrics, SLAController, SLATarget
from .paged_cache import TRASH_PAGE, PageAllocator, paged_insert, pages_needed
from .params import (GREEDY, EngineSaturated, Request, RequestOutput,
                     RequestStats, RoundBudgetExhausted, SamplingParams)
from .sampler import ERR_TOKEN, sample_tokens, sample_tokens_scan
from .spec_decode import DraftArm, accept_longest_prefix

__all__ = ["ServeEngine", "greedy_generate", "translate"]

# families safe to prefill right-padded: attention caches with pos/len
# masking AND token-only prompts (vlm logits interleave image patches, so
# the last-real-token index is not lengths-derived; ssm/hybrid recurrent
# states would absorb pad tokens)
_PAD_SAFE = ("dense", "moe", "encdec", "audio")
# what ServeEngine._span hands an untraced engine: a shared no-op block
_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class _Slot:
    id: int
    tokens: list = dataclasses.field(default_factory=list)
    active: bool = False
    request: Optional[Request] = None
    seq: int = -1       # admission order (preemption picks the youngest)


class ServeEngine:
    """Fixed-slot continuous-batching engine with an internal queue.

    submit() enqueues a request (admitting it immediately if a slot is
    free); step() admits pending requests, runs one batched
    decode+sample step, retires finished slots, and returns their
    RequestOutputs; run_until_drained() loops step() until the queue
    and all slots are empty.

    The legacy slot-level surface (add_request / tick / result /
    free_slot) is kept as a thin shim over the request API.
    """

    def __init__(self, model, params, *, slots: int, max_len: int,
                 kv_dtype: str = "bf16", ctx: Optional[Ctx] = None,
                 paged: bool = False, page_size: int = 8,
                 num_pages: Optional[int] = None,
                 max_src_len: Optional[int] = None, horizon: int = 1,
                 draft: Optional[DraftArm] = None, overlap: bool = True,
                 sla: Optional[SLATarget] = None,
                 max_pending: Optional[int] = None,
                 preempt_limit: int = 3, faults=None, trace=None,
                 mesh=None):
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if preempt_limit < 0:
            raise ValueError(
                f"preempt_limit must be >= 0, got {preempt_limit}")
        self.model = model
        self.params = params
        self.ctx = ctx or Ctx()
        # tensor-parallel mesh: params and KV storage are device_put
        # once at init under NamedSharding (no per-round resharding);
        # every jitted callable traces under set_mesh(self.mesh) so the
        # model's hint() constraints resolve against it
        self.mesh = mesh
        if mesh is not None:
            # TP-only weight sharding (fsdp_scope="none"): FSDP would
            # split contraction dims over the data axis, reordering
            # float accumulation enough to flip sampled tokens — the
            # engine's standing invariant is token-identical streams,
            # and inference weights are read-only so FSDP buys nothing
            self.params = jax.device_put(
                self.params,
                param_shardings(mesh, self.params, fsdp_scope="none"))
        self.kv_dtype = kv_dtype
        self.max_len = max_len
        self.n_slots = slots
        self.horizon = int(horizon)
        fam = model.cfg.family
        self.enc_cap = int(max_src_len or getattr(model.cfg, "enc_len", 0)
                           or 0)
        self.paged = bool(paged)
        self.draft = draft
        self.draft_cache = None
        if draft is not None and fam not in _PAD_SAFE:
            raise ValueError(
                f"speculative decoding supports families {_PAD_SAFE}, got "
                f"{fam!r} (the draft/verify scans need pos/len-masked "
                "attention caches)")
        if self.paged:
            if fam not in _PAD_SAFE:
                raise ValueError(
                    f"paged serving supports families {_PAD_SAFE}, got "
                    f"{fam!r} (recurrent state is O(1) per sequence; vlm "
                    "prompt lengths are not lengths-derived)")
            self.page_size = int(page_size)
            self.max_pages = pages_needed(max_len, self.page_size)
            # a draft arm doubles the default pool: both arms reserve a
            # full chain per request out of the SAME allocator id space
            usable = num_pages if num_pages is not None \
                else slots * self.max_pages * (2 if draft else 1)
            self.allocator = PageAllocator(usable + 1, reserved=1)
            if fam in ("encdec", "audio"):
                self.cache = model.init_paged_cache(
                    slots, self.max_pages, usable + 1, self.page_size,
                    kv_dtype, enc_len=self.enc_cap)
                if draft is not None:
                    self.draft_cache = model.init_paged_cache(
                        slots, self.max_pages, usable + 1, self.page_size,
                        draft.kv_dtype, enc_len=self.enc_cap)
            else:
                self.cache = model.init_paged_cache(
                    slots, self.max_pages, usable + 1, self.page_size,
                    kv_dtype)
                if draft is not None:
                    self.draft_cache = model.init_paged_cache(
                        slots, self.max_pages, usable + 1, self.page_size,
                        draft.kv_dtype)
            self._chains: Dict[int, list] = {}      # request id -> pages
            self._draft_chains: Dict[int, list] = {}
        else:
            if fam in ("encdec", "audio"):
                self.cache = model.init_cache(slots, max_len, kv_dtype,
                                              enc_len=self.enc_cap)
                if draft is not None:
                    self.draft_cache = model.init_cache(
                        slots, max_len, draft.kv_dtype, enc_len=self.enc_cap)
            else:
                self.cache = model.init_cache(slots, max_len, kv_dtype)
                if draft is not None:
                    self.draft_cache = model.init_cache(
                        slots, max_len, draft.kv_dtype)
        if mesh is not None:
            # one-time placement of the KV storage: paged pools shard
            # their head axes (block tables / allocator stay replicated
            # host state), dense caches shard per cache_shardings
            shard = paged_pool_shardings if self.paged else cache_shardings
            self.cache = jax.device_put(self.cache, shard(mesh, self.cache))
            if self.draft_cache is not None:
                self.draft_cache = jax.device_put(
                    self.draft_cache, shard(mesh, self.draft_cache))
        self.slots = [_Slot(i) for i in range(slots)]
        self.cur = jnp.zeros((slots, 1), jnp.int32)
        # per-slot sampling state — traced args of the fused step, so
        # mixed SamplingParams across slots share one executable
        self._temps = jnp.zeros((slots,), jnp.float32)
        self._top_ks = jnp.zeros((slots,), jnp.int32)
        self._top_ps = jnp.ones((slots,), jnp.float32)
        self._keys = jnp.zeros((slots, 2), jnp.uint32)
        self._offsets = jnp.zeros((slots,), jnp.int32)

        self._queue: collections.deque = collections.deque()
        self._finished: List[RequestOutput] = []
        self._next_id = 0
        self._stats: Dict[int, RequestStats] = {}
        self._last_admitted_slot = -1
        self._decode_steps = 0            # occupancy accounting
        self._active_slot_steps = 0
        self._sampler_full_steps = 0      # micro-steps a live slot sampled
        self._page_slot_steps = 0
        self._decode_syncs = 0            # host-overhead accounting
        self._synced_tokens = 0
        self._verify_calls = 0            # speculative-decode accounting
        self._drafted = 0
        self._accepted = 0
        self._rejected = 0
        self.overlap = bool(overlap)      # dispatch horizon N+1 before
        self._overlap_rounds = 0          # ... syncing horizon N's block
        # slots (re)admitted since the last horizon dispatch: the carry
        # merge must take THEIR masks from host state, not the device
        self._dirty_slots: set = set()
        self.sla = (SLAController(sla, self.horizon, slots)
                    if sla is not None else None)
        # -- observability --------------------------------------------
        # trace is a Tracer, a TraceConfig (builds one), or None. Every
        # emission in the hot paths sits behind `if self.trace is not
        # None`, so the disabled path adds no allocations, clock reads,
        # or device syncs to the round loop.
        if isinstance(trace, TraceConfig):
            trace = Tracer(trace)
        self.trace: Optional[Tracer] = trace
        self._round_no = 0
        # ttft/tpot histograms record once per retirement — never in
        # the round loop — so latency percentiles in metrics() are free
        # and exist even when tracing is off. Phase timing fills only
        # under tracing (it needs extra perf_counter reads per phase);
        # a phase's total ms is its histogram's sum.
        self._ttft_hist = Histogram()
        self._tpot_hist = Histogram()
        self._phase_hist: Dict[str, Histogram] = {p: Histogram()
                                                  for p in PHASES}
        # -- fault tolerance ------------------------------------------
        self.max_pending = max_pending    # bounded admission queue
        self.preempt_limit = int(preempt_limit)
        self.faults = faults              # FaultPlan (serving/faults.py)
        if faults is not None:
            faults.reset()                # one plan per engine, from 0
        self._skew_s = 0.0                # fault-injected clock skew
        # on-demand paging: target-only paged engines allocate prefill
        # pages at admission and grow chains per dispatched horizon; a
        # draft arm keeps the whole-budget reservation (two rollback-
        # symmetric chains per request make mid-decode growth moot)
        self.on_demand = self.paged and draft is None
        self._admit_seq = 0               # victim ordering (youngest)
        self._preempted: Dict[int, list] = {}       # rid -> stashed tokens
        self._preempt_counts: Dict[int, int] = {}   # rid -> eviction count
        self._flow_ids: Dict[int, int] = {}         # rid -> open trace flow
        self._queued_at: Dict[int, float] = {}      # rid -> queued since
        self._disp_len: Dict[int, int] = {}  # slot -> dispatched positions
        self._no_poison = jnp.full((slots,), -1, jnp.int32)
        self._preemptions = 0
        self._resumed = 0
        self._deadline_expirations = 0
        self._admission_rejections = 0
        self._slot_errors = 0

        fam = model.cfg.family
        self._tkey = "tgt_in" if fam in ("encdec", "audio") else "tokens"
        self._bucketed = fam in _PAD_SAFE
        # dense attention caches accept an injected per-slot "active"
        # mask inside the horizon scan (paged caches carry one natively;
        # recurrent-state families neither need nor understand it — a
        # retired slot's state is resplice-overwritten at admission)
        self._mask_active = (not self.paged) and fam in _PAD_SAFE
        self._horizon_fns: Dict[int, Callable] = {}
        self.prefill_shapes: set = set()
        bucketed = self._bucketed

        def _prefill(p, batch, length, temp, top_k, top_p, key):
            one = model.init_cache(1, max_len, kv_dtype)
            one, logits = model.prefill(self.ctx, p, one, batch)
            # under bucketing the prompt is right-padded: the last real
            # token sits at length-1, not at the end of the logits
            last = logits[0, length - 1] if bucketed else logits[0, -1]
            last = last.astype(jnp.float32)
            tok = sample_tokens(last[None], temp[None], top_k[None],
                                top_p[None], key[None],
                                jnp.zeros((1,), jnp.int32))[0]
            return one, tok

        self._prefill_fn = self._jit(_prefill)

        def _step(p, cur, cache, temps, top_ks, top_ps, keys, offsets,
                  poison):
            cache, logits = model.decode_step(self.ctx, p, cur, cache)
            lg = logits[:, -1]
            # fault injection: slots the plan marked for this dispatch
            # read NaN logits — the sampler's guard turns that into the
            # ERR_TOKEN sentinel for that row only
            lg = jnp.where((poison == 0)[:, None], jnp.float32("nan"), lg)
            nxt = sample_tokens(lg, temps, top_ks, top_ps, keys, offsets)
            return cache, nxt

        self._step_fn = self._jit(_step)

        def _prefill_group(p, inputs, lengths, slot_ids, page_rows, cache):
            # batched prefill of one admission group into a prompt-sized
            # dense mini-cache, scattered into page chains / cross rows;
            # also returns the logits that choose each first token
            n, s_bucket = inputs[self._tkey].shape
            mini = model.init_cache(n, s_bucket, kv_dtype)
            mini, logits = model.prefill(self.ctx, p, mini, inputs)
            last = logits[jnp.arange(n), lengths - 1].astype(jnp.float32)
            cache = paged_insert(cache, mini, slot_ids, page_rows, lengths)
            return cache, last

        def _prefill_paged(p, inputs, lengths, slot_ids, page_rows, cache,
                           temps, top_ks, top_ps, keys):
            # one jitted call admits a whole group, first-token sampling
            # fused in
            cache, last = _prefill_group(p, inputs, lengths, slot_ids,
                                         page_rows, cache)
            toks = sample_tokens(last, temps, top_ks, top_ps, keys,
                                 jnp.zeros((last.shape[0],), jnp.int32))
            return cache, toks

        self._prefill_paged_fn = self._jit(_prefill_paged)

        def _forced(p, inputs, lengths, slot_ids, page_rows, cache, feed):
            cache, last = _prefill_group(p, inputs, lengths, slot_ids,
                                         page_rows, cache)
            _, logits = decode_block(model, self.ctx, p, feed, cache)
            return jnp.concatenate(
                [last[:, None], logits[slot_ids].astype(jnp.float32)], axis=1)

        self._forced_fn = self._jit(_forced)

        if draft is not None:
            # the draft arm's prefill mirrors the target's but discards
            # the sampled token — the first emitted token is the TARGET
            # prefill's (exactness), the draft only warms its own cache
            def _draft_prefill(p, batch):
                one = model.init_cache(1, max_len, draft.kv_dtype)
                one, _ = model.prefill(draft.ctx, p, one, batch)
                return one

            self._draft_prefill_fn = self._jit(_draft_prefill)

            def _draft_prefill_paged(p, inputs, lengths, slot_ids,
                                     page_rows, cache):
                n, s_bucket = inputs[self._tkey].shape
                mini = model.init_cache(n, s_bucket, draft.kv_dtype)
                mini, _ = model.prefill(draft.ctx, p, mini, inputs)
                return paged_insert(cache, mini, slot_ids, page_rows,
                                    lengths)

            self._draft_prefill_paged_fn = self._jit(_draft_prefill_paged)

            # constant sampling args for the draft scan: temperature 0
            # everywhere makes sample_tokens_scan a pure greedy argmax
            self._z_f = jnp.zeros((slots,), jnp.float32)
            self._z_i = jnp.zeros((slots,), jnp.int32)
            self._o_f = jnp.ones((slots,), jnp.float32)
            self._z_keys = jnp.zeros((slots, 2), jnp.uint32)
            self._no_eos = jnp.full((slots,), -1, jnp.int32)
            self._draft_fns: Dict[int, Callable] = {}
            self._verify_fns: Dict[int, Callable] = {}

    def _jit(self, fn):
        """jax.jit with the engine mesh active at trace *and* call time.

        hint()/hint_pick() constraints inside the model resolve against
        the contextvar mesh when the function is traced, so a mesh-less
        engine compiles exactly the executable it always did (set_mesh
        is a no-op wrapper only for mesh-armed engines)."""
        jitted = jax.jit(fn)
        if self.mesh is None:
            return jitted
        mesh = self.mesh

        def call(*args, **kwargs):
            with set_mesh(mesh):
                return jitted(*args, **kwargs)

        return call

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------

    def submit(self, request, params: Optional[SamplingParams] = None, *,
               on_token: Optional[Callable[[int], None]] = None) -> int:
        """Enqueue a request; returns its request id.

        ``request`` is a Request or a B=1 model batch dict; ``params``
        overrides the request's SamplingParams (default: greedy). On a
        dense engine the request is admitted immediately when a slot is
        free; on a paged engine admission happens at the next step() so
        a burst of submits lands as one batched multi-slot prefill.

        ``on_token`` (or ``Request.on_token``) is the streaming hook:
        called with each generated token id as the horizon block
        carrying it lands on the host — the first token fires during
        prefill admission, before submit() even returns on a dense
        engine. Callbacks run on the scheduler walk; keep them cheap.

        With ``max_pending`` set, a full admission queue raises the
        typed ``EngineSaturated`` (backpressure: retry after a step /
        stream round drains the queue) instead of growing unboundedly
        and failing later in the allocator.
        """
        if self.max_pending is not None \
                and len(self._queue) >= self.max_pending:
            self._admission_rejections += 1
            raise EngineSaturated(len(self._queue), self.max_pending)
        if not isinstance(request, Request):
            request = Request(inputs=dict(request), params=params or GREEDY)
        elif params is not None:
            request = dataclasses.replace(request, params=params)
        if on_token is not None:
            request = dataclasses.replace(request, on_token=on_token)
        toks = jnp.asarray(request.inputs[self._tkey])
        if toks.ndim == 1:
            toks = toks[None]
        prompt_len = int(toks.shape[1])
        budget = prompt_len + request.params.max_new_tokens
        if budget > self.max_len:
            raise ValueError(
                f"request needs prompt_len + max_new_tokens = {prompt_len} + "
                f"{request.params.max_new_tokens} = {budget} cache positions "
                f"but the engine was built with max_len={self.max_len}; "
                f"shorten the request or deploy with a larger max_len")
        if self.paged:
            arms = 2 if self.draft is not None else 1
            need = pages_needed(budget, self.page_size) * arms
            usable = self.allocator.capacity - self.allocator.reserved
            if need > usable:
                # fail fast: an unfittable reservation would block the
                # FIFO admission head forever, not just wait its turn
                raise ValueError(
                    f"request needs {need} KV pages"
                    + (" (target + draft arms)" if arms == 2 else "")
                    + f" but the pool holds only {usable}; deploy with "
                    f"num_pages>={need} or shorten the request")
        se = self._src_len(request.inputs)
        if se is not None and se > self.enc_cap:
            # shorter sources are fine (the per-slot cross cache is
            # allocated at enc_cap and masked by cross_len); longer ones
            # cannot fit the allocated cross-attention leaves
            raise ValueError(
                f"source length {se} exceeds the engine's cross-attention "
                f"capacity {self.enc_cap}; deploy with max_src_len>="
                f"{se} or shorten the source")
        request = dataclasses.replace(
            request, inputs={**request.inputs, self._tkey: toks},
            id=self._next_id)
        self._next_id += 1
        arrival = self._now()
        self._stats[request.id] = RequestStats(
            arrival_s=arrival, prompt_len=prompt_len)
        self._queued_at[request.id] = arrival
        if self.trace is not None:
            tid = request.id + 1
            self.trace.name_track(tid, f"req {request.id}")
            self.trace.begin(tid, "request", arrival, rid=request.id,
                             prompt_len=prompt_len,
                             max_new_tokens=request.params.max_new_tokens)
            self.trace.begin(tid, "queued", arrival)
        self._queue.append(request)
        if not self.paged:          # paged admission batches at step()
            self._admit_pending()
        return request.id

    def step(self, horizon: Optional[int] = None) -> List[RequestOutput]:
        """Admit pending requests, run one fused decode horizon, and
        return the RequestOutputs of every request finished this step.

        ``horizon=K`` fuses K decode+sample micro-steps into one jitted
        ``lax.scan`` and syncs the (K, slots) token block to the host
        once; ``horizon=1`` (and the engine default unless constructed
        otherwise) is the original per-token step, token-for-token
        identical to previous releases. The scan length is clamped to
        the power-of-two bucket of the largest remaining token budget
        among active slots, so an over-long horizon costs masked
        micro-steps only up to that bucket, never the full K. Admission
        is continuous but horizon-granular: every step first drains as
        much of the queue as freed slots (and, when paged, freed pages)
        allow, so slots refill at horizon boundaries instead of waiting
        for a full drain."""
        K = self._effective_horizon(horizon)
        if self.trace is not None:
            self._round_begin()
        self._round_boundary()
        n_active = sum(s.active for s in self.slots)
        if self._speculate_now():
            self._spec_round()
        elif n_active and K == 1:
            self._token_step()
        elif n_active:
            # clamp the scan to the (power-of-two-bucketed) largest
            # remaining budget among active slots: an over-long horizon
            # must not burn batched micro-steps every slot has already
            # retired out of, and bucketing keeps compiled scan lengths
            # bounded by log2(max_len), not one per distinct budget
            _, _, block, Kd, seqs = self._dispatch_horizon(
                min(K, self._bucket(self._max_rem())))
            self._walk_block(block, Kd, seqs)
        if self.trace is not None:
            self._round_end()
        return self._take_finished()

    def run_until_drained(self, max_steps: int = 1_000_000,
                          horizon: Optional[int] = None
                          ) -> List[RequestOutput]:
        """Serve every queued/in-flight request; returns all outputs.

        Thin wrapper over the overlapped round loop (``_rounds``):
        token-for-token identical to serial stepping at any horizon,
        but the host walk of each synced block runs while the next
        horizon is already dispatched on device (``overlap=False``
        restores the serial order). ``horizon`` overrides the engine
        default for every round."""
        outs: List[RequestOutput] = list(self._take_finished())
        for _ in self._rounds(horizon, max_rounds=max_steps):
            outs.extend(self._take_finished())
        outs.extend(self._take_finished())
        return outs

    def stream(self, horizon: Optional[int] = None,
               on_round: Optional[Callable[[], None]] = None,
               max_rounds: int = 1_000_000
               ) -> Iterator[RequestOutput]:
        """Serve until drained, yielding each RequestOutput as its
        request finishes (same overlapped loop as run_until_drained).

        ``on_round`` is called once after every scheduler round —
        external drivers inject new arrivals there (bench_serving
        ``--rate`` submits its Poisson arrivals from it), and work
        submitted by the callback keeps the loop alive. Note the
        callback never fires on an engine that is already drained at
        call time (the loop exits before its first round)."""
        yield from self._take_finished()
        for _ in self._rounds(horizon, max_rounds=max_rounds):
            if on_round is not None:
                on_round()
            yield from self._take_finished()
        yield from self._take_finished()

    def stream_request(self, request,
                       params: Optional[SamplingParams] = None,
                       horizon: Optional[int] = None) -> Iterator[int]:
        """Submit ONE request and yield its token ids as each horizon
        block lands; the finished RequestOutput is the generator's
        return value (``StopIteration.value``).

        Other in-flight requests keep being served while this one
        streams — their outputs stay claimable via run_until_drained()
        / stream(). If the request is aborted externally mid-stream the
        generator ends and returns None (abort() hands the output to
        its own caller)."""
        buf: List[int] = []
        rid = self.submit(request, params, on_token=buf.append)

        def claim():
            for i, o in enumerate(self._finished):
                if o.request_id == rid:
                    return self._finished.pop(i)
            return None

        out = claim()       # dense prefill may already have finished it
        while buf:
            yield buf.pop(0)
        rounds = self._rounds(horizon)
        try:
            while out is None:
                try:
                    next(rounds)
                except (StopIteration, RoundBudgetExhausted):
                    break   # drained (abort) or round budget exhausted
                while buf:
                    yield buf.pop(0)
                out = claim()
        finally:
            # closing the round loop walks any dispatched-ahead block,
            # so other slots' synced tokens are never dropped
            rounds.close()
        while buf:
            yield buf.pop(0)
        return out

    def serve_rounds(self, horizon: Optional[int] = None,
                     max_rounds: int = 1_000_000) -> Iterator[None]:
        """Round-granular view of the overlapped scheduler loop: each
        ``next()`` advances exactly one round (admit / dispatch-ahead /
        sync+walk) and finished outputs accumulate for
        :meth:`take_finished`. This is the cluster router's drain
        primitive — interleaving several replicas' generators means
        each host sync of one replica happens while every OTHER
        replica's dispatched horizon is still running on its own
        devices. Closing the generator early walks any
        dispatched-ahead block, leaving host state consistent."""
        return self._rounds(horizon, max_rounds=max_rounds)

    def take_finished(self) -> List[RequestOutput]:
        """Claim (and clear) the outputs of every request that finished
        since the last claim — the companion to :meth:`serve_rounds`
        (``step``/``run_until_drained``/``stream`` claim internally)."""
        return self._take_finished()

    def _take_finished(self) -> List[RequestOutput]:
        out, self._finished = self._finished, []
        return out

    def teacher_forced_logits(self, requests, tokens) -> jax.Array:
        """Logits of this engine's own routes under teacher forcing.

        Admits ``requests`` (B=1 batch dicts, as ``submit`` takes them)
        as ONE prefill group into slots 0..n-1, as a burst of them
        admits, then feeds ``tokens`` (n, T) through ``decode_block``
        on the engine's page pool, Ctx kernel routes and mesh. Row t of
        the (n, T, vocab) f32 result holds the logits that choose token
        t given the prompt and ``tokens[:, :t]``: the prefill's for
        t = 0, a decode step's after. Two engines scored on one stream
        show how far their numerics part at every step, where their own
        greedy streams would part at the first near tie. Needs an idle
        paged engine without a draft arm; leaves it as it was.
        """
        if not self.paged or self.draft is not None:
            raise ValueError("teacher_forced_logits needs a paged engine "
                             "without a draft arm")
        if self.num_active or self.num_pending:
            raise ValueError("teacher_forced_logits needs an idle engine")
        tokens = np.asarray(tokens, np.int32)
        n, T = tokens.shape
        if len(requests) != n or not 0 < n <= self.n_slots:
            raise ValueError(f"{len(requests)} requests and {n} token rows; "
                             f"need the same number, 1 to {self.n_slots}")
        toks = [jnp.atleast_2d(jnp.asarray(r[self._tkey])) for r in requests]
        lens = [t.shape[1] for t in toks]
        if max(lens) + T - 1 > self.max_len:
            raise ValueError(f"prompt {max(lens)} + {T - 1} forced tokens "
                             f"exceed max_len={self.max_len}")
        chains = [self.allocator.alloc_chain(
            pages_needed(n_tok + T - 1, self.page_size)) for n_tok in lens]
        try:
            rows = np.zeros((n, self.max_pages), np.int32)
            for i, chain in enumerate(chains):
                rows[i, :len(chain)] = chain
            feed = np.zeros((self.n_slots, T - 1), np.int32)
            feed[:n] = tokens[:, :-1]
            return self._forced_fn(
                self.params, self._group_inputs(toks, requests),
                jnp.asarray(lens, jnp.int32), jnp.arange(n, dtype=jnp.int32),
                jnp.asarray(rows), self.cache, jnp.asarray(feed))
        finally:
            for chain in chains:
                self.allocator.free_chain(chain)

    def _now(self) -> float:
        """The engine clock: wall time plus any fault-injected skew
        (FaultPlan deadline tests advance time without sleeping)."""
        return time.perf_counter() + self._skew_s

    def _span(self, name: str, tid: int = SCHED_TID, **args):
        """The block of one scheduler phase (tid 0, its duration fed to
        the phase's histogram) or admission sub-span (``ADMIT_TID``) as
        a ``Tracer.span``: a complete event plus a profiler annotation
        of the same name. Durations come from raw perf_counter deltas so
        a fault-injected skew jump inside a phase (faults tick during
        "admit") cannot inflate it; the event timestamp is anchored on
        the engine clock so the trace timeline still shows the skew. An
        untraced engine gets a shared no-op block: no clock read, no
        call into the tracer or the profiler."""
        if self.trace is None:
            return _NO_SPAN
        return self.trace.span(tid, name, self._now,
                               self._phase_hist.get(name), **args)

    def _round_begin(self) -> None:
        self._round_no += 1
        self.trace.begin(SCHED_TID, "round", self._now(), n=self._round_no)

    def _round_end(self) -> None:
        self.trace.end(SCHED_TID, "round", self._now())

    def _round_boundary(self) -> None:
        """Host-side work at every scheduler round boundary: tick the
        fault plan (release/steal pages, skew the clock), expire
        deadlines, then admit from the queue. Runs on no-op rounds too,
        so transient faults clear and expired queued requests drain
        even when nothing is decoding."""
        with self._span("admit"):
            if self.faults is not None:
                with self._span("admit.faults", ADMIT_TID):
                    self.faults.on_round(self)
            with self._span("admit.expire", ADMIT_TID):
                self._expire_deadlines()
            self._admit_pending()

    def _deadline_passed(self, request: Request, now: float) -> bool:
        dl = request.params.deadline_ms
        if dl is None:
            return False
        return (now - self._stats[request.id].arrival_s) * 1e3 > dl

    def _expire_deadlines(self) -> None:
        """Retire every request (active or queued) whose deadline_ms
        elapsed — a pure host-clock compare at round boundaries, no
        extra device sync. Active slots free their pages through the
        ordinary _retire path; their tokens are truncated at the last
        synced position exactly like an abort."""
        now = self._now()
        for s in self.slots:
            if s.active and self._deadline_passed(s.request, now):
                self._retire(s, "deadline")
        if self._queue:
            keep = collections.deque()
            for r in self._queue:
                if self._deadline_passed(r, now):
                    self._finished.append(self._finish_queued(r, "deadline"))
                else:
                    keep.append(r)
            self._queue = keep

    def _finish_queued(self, r: Request, reason: str) -> RequestOutput:
        """Finish a request that is not (or no longer) in a slot —
        queued at expiry/abort, possibly with tokens stashed from an
        earlier preemption."""
        st = self._stats.pop(r.id)
        toks = self._preempted.pop(r.id, [])
        self._preempt_counts.pop(r.id, None)
        fid = self._flow_ids.pop(r.id, None)
        st.finished_s = self._now()
        st.queued_s += st.finished_s - self._queued_at.pop(r.id)
        if st.first_token_s == 0.0:
            st.first_token_s = st.finished_s
        st.new_tokens = len(toks)
        if reason == "deadline":
            self._deadline_expirations += 1
        if self.trace is not None:
            tid = r.id + 1
            self.trace.end(tid, "queued", st.finished_s)
            if fid is not None:
                # stashed request died before its resume: terminate the
                # residency link at the retirement instead
                self.trace.flow_end(tid, "resume", st.finished_s, fid,
                                    reason=reason)
            if reason == "deadline":
                self.trace.instant(tid, "deadline", st.finished_s)
            self.trace.instant(tid, "retired", st.finished_s,
                               reason=reason, tokens=st.new_tokens)
            self.trace.end(tid, "request", st.finished_s)
        return RequestOutput(r.id, r.inputs, list(toks), reason, st)

    def _effective_horizon(self, horizon: Optional[int]) -> int:
        """Resolve one round's horizon: explicit arg > SLA controller >
        engine default."""
        if horizon is not None:
            K = int(horizon)
        elif self.sla is not None:
            K = self.sla.horizon
        else:
            K = self.horizon
        if K < 1:
            raise ValueError(f"horizon must be >= 1, got {K}")
        return K

    def _speculate_now(self) -> bool:
        # speculative rounds need exact-match acceptance, which only
        # reproduces greedy sampling: any sampled request in the batch
        # falls the whole step back to the target-only path (the draft
        # cache goes stale — harmless, verification is target-owned)
        return (self.draft is not None
                and any(s.active for s in self.slots)
                and all(s.request.params.greedy
                        for s in self.slots if s.active))

    def _max_rem(self) -> int:
        """Largest remaining token budget among active slots (host view)."""
        rems = [s.request.params.max_new_tokens - len(s.tokens)
                for s in self.slots if s.active]
        return max(rems) if rems else 0

    def _emit(self, s: _Slot, tok: int, synced: bool = True) -> None:
        """Deliver one token to a slot's request: append, count, fire
        the streaming callback, retire on EOS/budget. ``synced=False``
        marks the prefill-produced first token (it never crossed the
        decode sync path). The ERR_TOKEN sentinel (non-finite logits —
        see sampler.py) is never delivered: it retires ONLY this slot
        with finish_reason "error" and its partial tokens, while the
        rest of the fused batch keeps decoding."""
        if tok == ERR_TOKEN:
            self._retire(s, "error")
            return
        s.tokens.append(tok)
        if synced:
            self._synced_tokens += 1
        cb = s.request.on_token
        if cb is not None:
            cb(tok)
        if s.active:    # the callback may have aborted its own request
            self._maybe_retire(s)

    def _token_step(self) -> None:
        """The legacy horizon=1 path: one fused decode+sample dispatch,
        one host sync per token."""
        tr = self.trace
        with self._span("dispatch", K=1):
            self._grow_chains(1)
            self._decode_steps += 1
            self._sampler_full_steps += self._sampling()
            self._active_slot_steps += sum(s.active for s in self.slots)
            if self.paged:
                self._page_slot_steps += self.allocator.pages_in_use
            self.cache, nxt = self._step_fn(
                self.params, self.cur, self.cache, self._temps,
                self._top_ks, self._top_ps, self._keys, self._offsets,
                self._poison_arr(1))
            self._note_dispatched(1)
            self.cur = nxt[:, None]
            self._offsets = self._offsets + 1
        with self._span("sync", K=1):
            self._decode_syncs += 1
            nxt_host = np.asarray(nxt)          # one sync per token
        with self._span("walk"):
            for s in self.slots:
                if s.active:
                    if tr is not None:
                        tr.instant(s.request.id + 1, "decode-round",
                                   self._now(), planned=1)
                    self._emit(s, int(nxt_host[s.id]))

    def _sampling(self) -> bool:
        """Whether an active slot samples (``temperature > 0``), so the
        sampler's full path runs; a greedy batch takes its argmax only."""
        return any(s.active and not s.request.params.greedy
                   for s in self.slots)

    def _dispatch_horizon(self, K: int, carry=None):
        """Dispatch one K-step fused horizon WITHOUT syncing its block.

        Returns ``(alive, rem, block, K)`` — all device handles except
        K. ``carry=None`` builds the scan masks from host slot state
        (the serial path). ``carry=(alive, rem)`` reuses the previous
        dispatch's device-side final carry, so this scan launches while
        the host is still walking that block: the in-scan retirement
        rule computes exactly the alive/rem the host walk will arrive
        at for continuing slots. Slots touched since that dispatch are
        merged from host state — fresh admissions override with their
        own masks (the carry says dead), aborts force alive to 0 via
        the min (their in-flight micro-steps waste masked compute
        only). eos/sampling arrays are always host-rebuilt: stale
        values sit behind a zero alive mask.

        On-demand paged engines first grow every active chain to cover
        the K micro-steps (preempting victims on exhaustion — see
        _grow_chains), so block tables are static across the scan
        whichever allocation mode is live.
        """
        with self._span("dispatch", K=K):
            self._grow_chains(K)
            self._decode_steps += K
            self._sampler_full_steps += K * self._sampling()
            if self.paged:
                self._page_slot_steps += K * self.allocator.pages_in_use
            fn = self._horizon_fns.get(K)
            if fn is None:
                fn = self._horizon_fns[K] = self._make_horizon_fn(K)
            alive_h, rem_h, eos = self._scan_masks()
            if carry is None:
                alive, rem = alive_h, rem_h
            else:
                alive_c, rem_c = carry
                fresh = np.zeros((self.n_slots,), bool)
                for sid in self._dirty_slots:
                    fresh[sid] = True
                fresh = jnp.asarray(fresh)
                alive = jnp.where(fresh, alive_h,
                                  jnp.minimum(alive_c, alive_h))
                rem = jnp.where(fresh, rem_h, rem_c)
            self._dirty_slots.clear()
            # dispatch-time occupancy snapshot: which request generation
            # each slot row of this block belongs to (see _walk_block)
            seqs = tuple(s.seq if s.active else -1 for s in self.slots)
            self.cache, self.cur, self._offsets, alive_o, rem_o, block = fn(
                self.params, self.cur, self.cache, self._temps,
                self._top_ks, self._top_ps, self._keys, self._offsets,
                alive, rem, eos, self._poison_arr(K))
            self._note_dispatched(K)
        return alive_o, rem_o, block, K, seqs

    def _walk_block(self, block, K: int, seqs=None) -> None:
        """Sync one dispatched (K, slots) token block and walk it on
        the host: emit/stream/retire exactly as the serial horizon
        path. A block every slot already retired out of (possible for a
        dispatched-ahead horizon that an EOS invalidated) is dropped
        without syncing.

        ``seqs`` is the per-slot admission-sequence snapshot taken when
        the block was dispatched: a slot's rows are walked only if its
        CURRENT occupant is the same request generation the block was
        computed for. Between dispatch and walk the occupant can change
        — retire on deadline, get aborted, or be preempted for pages,
        with a new request (or the same one, resumed) admitted into the
        freed slot — and without the gate the new occupant would swallow
        the stale rows (pads after an in-scan retirement, or the dead
        request's never-observed continuation after an abort)."""
        eligible = [s for s in self.slots
                    if s.active and (seqs is None or seqs[s.id] == s.seq)]
        if not eligible:
            return
        tr = self.trace
        with self._span("sync", K=K):
            self._decode_syncs += 1
            blk = np.asarray(block)             # one sync per horizon
        with self._span("walk"):
            for s in eligible:
                if not s.active:    # retired by a groupmate's callback
                    continue
                if tr is not None:
                    tr.instant(s.request.id + 1, "decode-round",
                               self._now(), planned=K)
                for t in range(K):              # walk until retirement
                    self._active_slot_steps += 1
                    self._emit(s, int(blk[t, s.id]))
                    if not s.active:
                        break

    def _ahead_horizon(self, K_cfg: int, Kd: int) -> int:
        """Length of the next scan to dispatch before walking the
        in-flight Kd-step block, or 0 to stay serial. Dispatch-ahead
        only pays when some slot's budget outlasts the in-flight block
        (otherwise the extra scan is all-masked waste and would skew
        sync counts vs the serial engine); a draft arm disables it —
        speculative rounds are host decision points and remain the
        faster path for greedy batches."""
        if not self.overlap or K_cfg <= 1 or self.draft is not None:
            return 0
        rem_after = self._max_rem() - Kd
        if rem_after <= 0:
            return 0
        return min(K_cfg, self._bucket(rem_after))

    def _rounds(self, horizon: Optional[int] = None,
                max_rounds: int = 1_000_000) -> Iterator[None]:
        """The overlapped scheduler loop; yields once per round.

        Round shape: admit pending prompts into freed slots, then —
        with a block in flight — dispatch the NEXT horizon from the
        in-flight scan's device carry and only then sync+walk the
        block, so the Python walk (retire, stream callbacks, admission)
        overlaps the device's work on horizon N+1. Speculative rounds
        and horizon=1 run serially through their legacy paths (still
        streaming). Finished outputs accumulate in ``_finished`` for
        the caller to claim between rounds; closing the generator early
        walks any dispatched-ahead block first, so engine host state
        stays consistent with the device."""
        pending = None
        rounds = 0
        try:
            while True:
                tr = self.trace
                if tr is not None:
                    self._round_begin()
                self._round_boundary()
                if (pending is None and not self._queue
                        and not any(s.active for s in self.slots)):
                    if tr is not None:
                        self._round_end()
                    return
                rounds += 1
                if rounds > max_rounds:
                    if tr is not None:
                        self._round_end()
                    raise RoundBudgetExhausted(
                        "run_until_drained did not converge")
                if pending is not None:
                    alive_d, rem_d, block, Kd, seqs = pending
                    pending = None
                    nk = self._ahead_horizon(
                        self._effective_horizon(horizon), Kd)
                    if nk:
                        pending = self._dispatch_horizon(
                            nk, carry=(alive_d, rem_d))
                        self._overlap_rounds += 1
                    self._walk_block(block, Kd, seqs)
                elif any(s.active for s in self.slots):
                    K = self._effective_horizon(horizon)
                    if self._speculate_now():
                        self._spec_round()
                    elif K == 1:
                        self._token_step()
                    else:
                        pending = self._dispatch_horizon(
                            min(K, self._bucket(self._max_rem())))
                        if not self.overlap:
                            _, _, block, Kd, seqs = pending
                            pending = None
                            self._walk_block(block, Kd, seqs)
                # else: queue blocked with nothing active — a no-op
                # round; the round budget turns a livelock into the
                # legacy non-convergence error
                if tr is not None:
                    self._round_end()
                yield
        finally:
            if pending is not None:
                self._walk_block(pending[2], pending[3], pending[4])

    def abort(self, request_id: int) -> Optional[RequestOutput]:
        """Cancel a queued or in-flight request. Returns its output
        (finish_reason 'abort') directly, or None if unknown.

        Under horizon-fused decode the request's tokens are truncated
        at the last *synced* position (slot token lists only ever hold
        synced tokens — any micro-steps the device ran past that point
        were never observed and are discarded); the page chain is freed
        exactly once, by the same _retire path every finish reason
        uses — a second abort of the same id returns None instead of
        double-freeing. A queued request that was previously preempted
        returns its stashed tokens; one still waiting in an admission
        group's activation loop is found active (every group slot goes
        live before any first-token callback fires — see
        _admit_group), so callback-driven aborts of groupmates retire
        them instead of leaving a dead slot to be served then thrown
        away."""
        for i, r in enumerate(self._queue):
            if r.id == request_id:
                del self._queue[i]
                return self._finish_queued(r, "abort")
        for s in self.slots:
            if s.active and s.request.id == request_id:
                self._retire(s, "abort")
                return self._finished.pop()
        return None

    @property
    def num_pending(self) -> int:
        return len(self._queue)

    @property
    def num_active(self) -> int:
        return sum(s.active for s in self.slots)

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shapes compiled so far (bucketing keeps this
        bounded by the bucket count, not the number of prompt lengths)."""
        return len(self.prefill_shapes)

    def metrics(self) -> EngineMetrics:
        """One frozen snapshot of every engine counter, ratio, and
        gauge — the single read surface for benchmarks, the eval suite,
        and launchers (the individual properties remain for
        back-compat)."""
        return EngineMetrics(
            decode_steps=self._decode_steps,
            decode_syncs=self._decode_syncs,
            synced_tokens=self._synced_tokens,
            active_slot_steps=self._active_slot_steps,
            sampler_full_steps=self._sampler_full_steps,
            page_slot_steps=self._page_slot_steps,
            overlap_rounds=self._overlap_rounds,
            verify_calls=self._verify_calls,
            drafted_tokens=self._drafted,
            accepted_tokens=self._accepted,
            rejected_tokens=self._rejected,
            preemptions=self._preemptions,
            resumed_requests=self._resumed,
            deadline_expirations=self._deadline_expirations,
            admission_rejections=self._admission_rejections,
            slot_errors=self._slot_errors,
            mean_tokens_per_sync=self.mean_tokens_per_sync,
            occupancy=self.occupancy,
            page_utilization=self.page_utilization,
            acceptance_rate=self.acceptance_rate,
            mean_accepted_per_verify=self.mean_accepted_per_verify,
            ttft_p50_ms=round(self._ttft_hist.percentile(50.0), 4),
            ttft_p95_ms=round(self._ttft_hist.percentile(95.0), 4),
            tpot_p50_ms=round(self._tpot_hist.percentile(50.0), 4),
            tpot_p95_ms=round(self._tpot_hist.percentile(95.0), 4),
            phase_admit_ms=round(self._phase_hist["admit"].total, 4),
            phase_dispatch_ms=round(self._phase_hist["dispatch"].total, 4),
            phase_sync_ms=round(self._phase_hist["sync"].total, 4),
            phase_walk_ms=round(self._phase_hist["walk"].total, 4),
            kv_cache_bytes=self.kv_cache_bytes,
            prefill_compiles=self.prefill_compiles)

    def prometheus(self) -> str:
        """Prometheus text exposition of the current metrics()
        snapshot plus the latency and round-phase histograms (bucket
        series are only non-empty where the engine recorded: ttft/tpot
        always, phases on traced engines)."""
        hists = {"ttft_ms": self._ttft_hist, "tpot_ms": self._tpot_hist}
        for p in PHASES:
            hists[f"round_phase_{p}_ms"] = self._phase_hist[p]
        return render_prometheus(self.metrics(), hists)

    def latency_histograms(self) -> Dict[str, Histogram]:
        """The live TTFT/TPOT Histogram accumulators (one sample per
        retirement since the last reset). Cluster-level aggregation
        merges these across replicas via ``Histogram.merge`` — merge
        into a fresh ``Histogram()``, never in place, or the replica's
        own percentiles double-count."""
        return {"ttft_ms": self._ttft_hist, "tpot_ms": self._tpot_hist}

    def reset_metrics(self) -> None:
        """Zero every EngineMetrics counter (occupancy/page-utilization/
        host-sync/overlap/speculative-decode accumulators — e.g. after a
        warmup pass, so reported numbers cover only the measured run).
        The EngineMetrics.GAUGES fields are live state, not accumulation,
        and are unaffected."""
        self._decode_steps = 0
        self._active_slot_steps = 0
        self._sampler_full_steps = 0
        self._page_slot_steps = 0
        self._decode_syncs = 0
        self._synced_tokens = 0
        self._overlap_rounds = 0
        self._verify_calls = 0
        self._drafted = 0
        self._accepted = 0
        self._rejected = 0
        self._preemptions = 0
        self._resumed = 0
        self._deadline_expirations = 0
        self._admission_rejections = 0
        self._slot_errors = 0
        self._ttft_hist.reset()
        self._tpot_hist.reset()
        for h in self._phase_hist.values():
            h.reset()

    @property
    def preemptions(self) -> int:
        """Requests evicted from a slot for page pressure (each either
        resumed later via prefill-replay or, past preempt_limit,
        retired as 'preempted_limit')."""
        return self._preemptions

    @property
    def resumed_requests(self) -> int:
        """Preempted requests re-admitted via prefill-replay."""
        return self._resumed

    @property
    def deadline_expirations(self) -> int:
        """Requests retired because deadline_ms elapsed."""
        return self._deadline_expirations

    @property
    def admission_rejections(self) -> int:
        """submit() calls bounced with EngineSaturated (max_pending)."""
        return self._admission_rejections

    @property
    def slot_errors(self) -> int:
        """Slots failed by the non-finite-logits guard (finish_reason
        'error') while their batch kept decoding."""
        return self._slot_errors

    @property
    def overlap_rounds(self) -> int:
        """Rounds where the next horizon was dispatched before the
        previous block's host sync — each one is a host walk whose cost
        the device hid behind real work (the overlap tripwire metric)."""
        return self._overlap_rounds

    @property
    def verify_calls(self) -> int:
        """Speculative verify rounds run — each is ONE batched target
        forward over a drafted block, the denominator of the
        forwards-per-token win speculation exists to deliver."""
        return self._verify_calls

    @property
    def drafted_tokens(self) -> int:
        return self._drafted

    @property
    def accepted_tokens(self) -> int:
        return self._accepted

    @property
    def rejected_tokens(self) -> int:
        return self._rejected

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target verify accepted (the
        draft-quality metric; 0.0 before any speculative round)."""
        if not self._drafted:
            return 0.0
        return self._accepted / self._drafted

    @property
    def mean_accepted_per_verify(self) -> float:
        """Accepted draft tokens per verify round, summed over slots —
        how much draft work each batched target forward converts into
        output (on top of the 1 token/slot a round always emits)."""
        if not self._verify_calls:
            return 0.0
        return self._accepted / self._verify_calls

    @property
    def decode_steps(self) -> int:
        """Decode micro-steps the engine has run (each processes one
        token position per slot through the target or draft model). At
        horizon=1 on a target-only engine this equals the number of
        batched target-model forward dispatches — the baseline the
        speculative ``verify_calls`` count is measured against."""
        return self._decode_steps

    @property
    def decode_syncs(self) -> int:
        """Device->host syncs the decode loop has performed: one per
        step() at horizon=1, one per *horizon* when fused — the
        dispatch-overhead metric the horizon knob exists to shrink."""
        return self._decode_syncs

    @property
    def mean_tokens_per_sync(self) -> float:
        """Generated tokens delivered per host sync. At horizon=1 this
        is the mean number of busy slots (each sync carries one token
        per active slot); fusing multiplies it by up to the horizon —
        compare runs at equal occupancy to isolate the fusion win."""
        if not self._decode_syncs:
            return 0.0
        return self._synced_tokens / self._decode_syncs

    @property
    def occupancy(self) -> float:
        """Mean fraction of decode slots active per step served so far."""
        if not self._decode_steps:
            return 0.0
        return self._active_slot_steps / (self._decode_steps * self.n_slots)

    @property
    def page_utilization(self) -> float:
        """Mean fraction of the page pool in use per decode step."""
        if not self.paged or not self._decode_steps:
            return 0.0
        usable = self.allocator.capacity - self.allocator.reserved
        return self._page_slot_steps / (self._decode_steps * usable)

    @property
    def kv_cache_bytes(self) -> int:
        """Allocated KV-cache storage (the paged/dense memory knob),
        including the draft arm's cache when speculating."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.cache):
            total += leaf.size * leaf.dtype.itemsize
        if self.draft_cache is not None:
            for leaf in jax.tree_util.tree_leaves(self.draft_cache):
                total += leaf.size * leaf.dtype.itemsize
        return total

    # ------------------------------------------------------------------
    # legacy slot-level surface (kept for pre-request-API callers)
    # ------------------------------------------------------------------

    def add_request(self, batch_one: dict, gen_tokens: int) -> int:
        """Legacy: greedy request into a free slot; returns the slot id."""
        # queued work would claim the free slot first: admission wouldn't
        # be synchronous, so the legacy contract can't be honoured
        if self._queue or self.free_slot() is None:
            raise RuntimeError("no free slots")
        rid = self.submit(batch_one, SamplingParams(max_new_tokens=gen_tokens))
        if self.paged:
            self._admit_pending()        # legacy contract: admit now
        if self._queue:                  # paged: page pool exhausted
            self.abort(rid)
            raise RuntimeError("no free pages")
        return self._last_admitted_slot

    def tick(self) -> List[int]:
        """Legacy: one step; returns the slot ids finished this step."""
        return [o.slot for o in self.step()]

    def result(self, slot: int) -> list:
        """Legacy: generated token ids of the request last served in
        ``slot`` (also available on RequestOutput.token_ids)."""
        return self.slots[slot].tokens

    def free_slot(self) -> Optional[int]:
        for s in self.slots:
            if not s.active:
                return s.id
        return None

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _scan_masks(self):
        """Per-slot (alive, remaining-budget, eos-id) arrays for one
        horizon, rebuilt from host slot state at every boundary (all
        traced args — values never trigger a recompile)."""
        alive = np.zeros((self.n_slots,), np.int32)
        rem = np.zeros((self.n_slots,), np.int32)
        eos = np.full((self.n_slots,), -1, np.int32)
        for s in self.slots:
            if not s.active:
                continue
            sp = s.request.params
            alive[s.id] = 1
            rem[s.id] = sp.max_new_tokens - len(s.tokens)
            if sp.eos_id is not None:
                eos[s.id] = sp.eos_id
        return jnp.asarray(alive), jnp.asarray(rem), jnp.asarray(eos)

    def _make_horizon_fn(self, K: int, ctx: Optional[Ctx] = None):
        """Compile the K-step fused decode scan.

        Carry: (cache, cur, offsets, alive, rem); emits the (K, slots)
        token block the host syncs once per horizon. Retirement is an
        in-scan mask: a slot that emits its eos_id or exhausts its
        budget keeps decoding into masked positions (``active`` -> 0
        freezes its ``len`` and, when paged, routes its writes to the
        trash page) and pads the rest of its block row. Block tables
        are static across the scan — every admitted request holds its
        full page budget (see _request_pages).

        ``ctx`` overrides the engine Ctx — the speculative draft scan
        reuses this exact compiled shape against the draft arm's ctx,
        params, and cache (params and cache are traced arguments).

        The scan's FINAL alive/rem carry is returned alongside the
        block: it equals the host's post-walk view of the slots (same
        EOS/budget rule), which is what lets the overlapped loop
        dispatch horizon N+1 from it before the host has walked N.
        """
        model, ctx = self.model, ctx or self.ctx
        set_active = self._mask_active or self.paged
        strip_active = self._mask_active   # dense caches: key is transient

        def _horizon(p, cur, cache, temps, top_ks, top_ps, keys, offsets,
                     alive, rem, eos_ids, poison):
            def body(carry, i):
                cache, cur, offsets, alive, rem = carry
                if set_active:
                    cache = dict(cache, active=alive)
                cache, logits = model.decode_step(ctx, p, cur, cache)
                if strip_active:
                    cache = {k: v for k, v in cache.items() if k != "active"}
                lg = logits[:, -1]
                # fault injection: slots scheduled for micro-step i read
                # NaN logits; the sampler guard emits ERR_TOKEN for that
                # row only, and the in-scan retirement below kills the
                # slot exactly like the host walk will
                lg = jnp.where((poison == i)[:, None], jnp.float32("nan"),
                               lg)
                tok = sample_tokens_scan(lg, temps, top_ks,
                                         top_ps, keys, offsets, alive)
                rem = rem - alive
                hit_eos = (alive > 0) & (eos_ids >= 0) & (tok == eos_ids)
                alive = jnp.where(hit_eos | (rem <= 0) | (tok == ERR_TOKEN),
                                  0, alive)
                return (cache, tok[:, None], offsets + 1, alive, rem), tok

            (cache, cur, offsets, alive, rem), block = jax.lax.scan(
                body, (cache, cur, offsets, alive, rem),
                jnp.arange(K, dtype=jnp.int32))
            return cache, cur, offsets, alive, rem, block

        return self._jit(_horizon)

    # -- speculative decode (quantized-draft) --------------------------

    def _make_verify_fn(self, K: int):
        """Compile the speculative verify: ONE batched target forward
        over the drafted block (a fused teacher-forced K-step replay of
        ``decode_step``), longest-matching-prefix acceptance, and the
        shared rollback that truncates BOTH arms' caches to the emitted
        length. Everything device-side; the host syncs only the emitted
        block + per-slot counts, once per round."""
        model, ctx = self.model, self.ctx
        set_active = self._mask_active or self.paged
        strip_active = self._mask_active

        def _rollback(c, roll):
            # both arms wrote exactly K positions this round; keep the
            # first n_emit of them. Dense caches also re-mask `pos` so
            # rolled-back positions read as invalid (-1) in attention.
            new = dict(c)
            new_len = c["len"] - roll
            new["len"] = new_len
            if "pos" in c:
                idx = jnp.arange(c["pos"].shape[1], dtype=c["pos"].dtype)
                new["pos"] = jnp.where(idx[None, :] >= new_len[:, None],
                                       -1, c["pos"])
            return new

        def _verify(p, cur, cache, dcache, block, alive):
            # teacher-forced feed: the pending token, then the first
            # K-1 drafts — position i's logits are the target's choice
            # given prefix (.., cur, d_0..d_{i-1})
            feed = jnp.concatenate(
                [cur, jnp.swapaxes(block[:K - 1], 0, 1)], axis=1)
            if set_active:
                cache = dict(cache, active=alive)
            cache, logits = decode_block(model, ctx, p, feed, cache)
            if strip_active:
                cache = {k: v for k, v in cache.items() if k != "active"}
            lg32 = logits.astype(jnp.float32)
            tgt = jnp.argmax(lg32, axis=-1)
            tgt = jnp.swapaxes(tgt, 0, 1).astype(block.dtype)   # (K, S)
            out, n_emit, acc, new_cur = accept_longest_prefix(
                block, tgt, alive)
            # poisoned-slot isolation on the verify path: a slot whose
            # target logits went non-finite emits ONE ERR_TOKEN (the
            # host walk retires it as "error") and accepts nothing;
            # draft-side NaN needs no guard — a non-finite draft token
            # simply diverges from the finite target argmax and
            # acceptance stops there
            bad = (alive > 0) & ~jnp.all(jnp.isfinite(lg32), axis=(1, 2))
            n_emit = jnp.where(bad, 1, n_emit)
            acc = jnp.where(bad, 0, acc)
            out = jnp.where(bad[None, :] & (jnp.arange(K)[:, None] == 0),
                            jnp.asarray(ERR_TOKEN, block.dtype), out)
            roll = jnp.where(alive > 0, K - n_emit, 0)
            return (_rollback(cache, roll), _rollback(dcache, roll),
                    out, n_emit, acc, new_cur[:, None])

        return self._jit(_verify)

    def _spec_round(self):
        """One speculative round: draft K tokens with the horizon scan
        on the draft arm, verify them in one batched target forward,
        emit the longest matching prefix + the target's token at the
        first divergence (1..K tokens per live slot)."""
        draft = self.draft
        tr = self.trace
        max_rem = max(s.request.params.max_new_tokens - len(s.tokens)
                      for s in self.slots if s.active)
        K = max(1, min(draft.lookahead, self._bucket(max_rem)))
        with self._span("dispatch", K=K, spec=1):
            self._decode_steps += K
            if self.paged:
                self._page_slot_steps += K * self.allocator.pages_in_use
            dfn = self._draft_fns.get(K)
            if dfn is None:
                dfn = self._draft_fns[K] = self._make_horizon_fn(
                    K, ctx=draft.ctx)
            vfn = self._verify_fns.get(K)
            if vfn is None:
                vfn = self._verify_fns[K] = self._make_verify_fn(K)
            alive, _, _ = self._scan_masks()
            # the draft scan must not retire anyone — acceptance is the
            # verify pass's call: no EOS ids, budget that outlasts the
            # scan
            rem = (K + 1) * alive
            self.draft_cache, _, _, _, _, block = dfn(
                draft.params, self.cur, self.draft_cache, self._z_f,
                self._z_i, self._o_f, self._z_keys, self._z_i, alive, rem,
                self._no_eos, self._no_poison)
            self.cache, self.draft_cache, out, n_emit, acc, self.cur = vfn(
                self.params, self.cur, self.cache, self.draft_cache, block,
                alive)
            self._verify_calls += 1
        with self._span("sync", K=K):
            self._decode_syncs += 1
            blk = np.asarray(out)               # one sync per round
            n_emit = np.asarray(n_emit)
            acc = np.asarray(acc)
        with self._span("walk"):
            for s in self.slots:
                if not s.active:
                    continue
                a = int(acc[s.id])
                st = self._stats[s.request.id]
                st.drafted += K
                st.accepted += a
                st.rejected += K - a
                self._drafted += K
                self._accepted += a
                self._rejected += K - a
                if tr is not None:
                    tr.instant(s.request.id + 1, "verify", self._now(),
                               drafted=K, accepted=a,
                               emitted=int(n_emit[s.id]))
                for t in range(int(n_emit[s.id])):
                    self._active_slot_steps += 1
                    self._emit(s, int(blk[t, s.id]))
                    if not s.active:
                        break

    def _bucket(self, n: int) -> int:
        """Smallest power-of-two >= n, capped at max_len."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_len)

    @staticmethod
    def _src_len(inputs) -> Optional[int]:
        """Cross-attention source length of a request (None for LMs)."""
        if "src_tokens" in inputs:
            return int(jnp.asarray(inputs["src_tokens"]).shape[-1])
        if "frames" in inputs:
            return int(jnp.asarray(inputs["frames"]).shape[1])
        return None

    # -- fault tolerance: injection, on-demand paging, preemption ------

    def _poison_arr(self, K: int):
        """Per-dispatch NaN-injection schedule from the fault plan:
        entry s is the micro-step at which slot s's logits are forced
        non-finite (-1 = never). Always traced, so a clean dispatch and
        an injected one share the same executable."""
        if self.faults is None:
            return self._no_poison
        arr = self.faults.poison(self.n_slots, K)
        if arr is None:
            return self._no_poison
        if self.trace is not None:
            sched = np.asarray(arr, np.int32)
            self.trace.instant(
                SCHED_TID, "fault:nan", self._now(),
                slots=[int(i) for i in np.nonzero(sched >= 0)[0]])
        return jnp.asarray(np.asarray(arr, np.int32))

    def _pos_cap(self, request: Request) -> int:
        """Most cache positions a request can ever occupy (original
        prompt + its full token budget — a resumed request's replay
        feed is always shorter than this)."""
        return min(request.inputs[self._tkey].shape[1]
                   + request.params.max_new_tokens, self.max_len)

    def _note_dispatched(self, K: int) -> None:
        """Advance each active slot's dispatched-positions bound by the
        K micro-steps just launched (host upper bound on cache writes;
        mid-scan retirement only makes it conservative)."""
        if not self.on_demand:
            return
        for s in self.slots:
            if s.active:
                self._disp_len[s.id] = min(
                    self._disp_len[s.id] + K, self._pos_cap(s.request))

    def _grow_chains(self, K: int) -> None:
        """On-demand page allocation at a dispatch boundary: extend
        every active chain to cover the next K micro-steps, so block
        tables stay static across the scan. On pool exhaustion the
        lowest-priority / youngest request is preempted (possibly the
        grower itself) instead of raising — MemoryError never escapes
        the serving loop. Growth walks slots oldest/highest-priority
        first, so victims are exactly the requests admission would
        deprioritize."""
        if not self.on_demand:
            return
        for s in sorted((t for t in self.slots if t.active),
                        key=lambda t: (-t.request.params.priority, t.seq)):
            if not s.active:    # preempted as a victim earlier in this pass
                continue
            r = s.request
            want = min(self._disp_len[s.id] + K, self._pos_cap(r))
            chain = self._chains[r.id]
            while s.active:
                need = pages_needed(want, self.page_size) - len(chain)
                if need <= 0:
                    break
                got = self.allocator.try_alloc_chain(need)
                if got is not None:
                    start = len(chain)
                    chain.extend(got)
                    self.cache["block_tables"] = (
                        self.cache["block_tables"]
                        .at[s.id, start:start + len(got)]
                        .set(jnp.asarray(got, jnp.int32)))
                    break
                victim = min((t for t in self.slots if t.active),
                             key=lambda t: (t.request.params.priority,
                                            -t.seq))
                self._preempt(victim)

    def _preempt(self, s: _Slot) -> None:
        """Evict an in-flight request to relieve page pressure: stash
        its emitted tokens host-side, free its chain, and requeue it at
        the head for prefill-replay resume. The replay is provably
        token-identical — teacher-forced prefill is bit-exact vs
        incremental decode, and the per-token PRNG stream is
        offset-indexed — so survivors and resumed victims match an
        uncontended run token for token. Freeing mid-overlap is safe
        for the same reason abort is: device ops execute in submission
        order, so any stale in-flight writes to the freed pages land
        before the pages' next owner writes them. Past
        ``preempt_limit`` evictions the request retires as
        "preempted_limit" with its partial tokens instead of thrashing
        the pool forever."""
        r = s.request
        n = self._preempt_counts.get(r.id, 0) + 1
        self._preemptions += 1
        self._stats[r.id].preemptions = n
        if self.trace is not None:
            self.trace.instant(r.id + 1, "preempted", self._now(),
                               count=n, tokens=len(s.tokens))
        if n > self.preempt_limit:
            self._retire(s, "preempted_limit")
            return
        now = self._now()
        self._queued_at[r.id] = now
        if self.trace is not None:
            self.trace.begin(r.id + 1, "queued", now)
            # link the two slot residencies: flow_end fires at the
            # resume (or at retirement, if the stash dies queued), so
            # Perfetto draws the continuity arrow and Tracer.check()
            # can insist every preemption link is paired
            self._flow_ids[r.id] = self.trace.flow_start(
                r.id + 1, "resume", now, count=n)
        self._preempt_counts[r.id] = n
        self._preempted[r.id] = list(s.tokens)
        s.active = False
        s.request = None
        s.tokens = []
        self._disp_len.pop(s.id, None)
        self._dirty_slots.add(s.id)
        self.allocator.free_chain(self._chains.pop(r.id))
        self.cache["block_tables"] = \
            self.cache["block_tables"].at[s.id].set(TRASH_PAGE)
        self.cache["active"] = self.cache["active"].at[s.id].set(0)
        self.cache["len"] = self.cache["len"].at[s.id].set(0)
        self._queue.appendleft(r)

    def _feed_tokens(self, r: Request):
        """Prefill feed for a request: its prompt, extended with all
        but the last stashed token when resuming a preempted request
        (the last stashed token becomes the pending decode token — the
        exact slot state at eviction)."""
        toks = r.inputs[self._tkey]
        stash = self._preempted.get(r.id)
        if stash and len(stash) > 1:
            toks = jnp.concatenate(
                [toks, jnp.asarray(stash[:-1], jnp.int32)[None]], axis=1)
        return toks

    def _admit_pending(self):
        if not self.paged:
            while self._queue and self.free_slot() is not None:
                self._admit(self._queue.popleft())
            return
        while self._queue:
            with self._span("admit.group", ADMIT_TID):
                group = self._take_group()
            if not group:
                break
            self._admit_group(group)

    # -- paged admission -----------------------------------------------

    def _arm_pages(self, request: Request) -> int:
        """Pages one KV arm reserves at admission under whole-budget
        reservation (draft-armed engines): the full prompt+decode
        budget, so the request can never hit page pressure mid-decode.
        On-demand engines instead admit with prefill pages only (see
        _admit_pages) and grow per dispatch, preempting on exhaustion."""
        budget = (request.inputs[self._tkey].shape[1]
                  + request.params.max_new_tokens)
        return pages_needed(min(budget, self.max_len), self.page_size)

    def _admit_pages(self, request: Request) -> int:
        """Pages admission must allocate for one request right now:
        just the prefill feed when on-demand (decode pages come later,
        per dispatched horizon), the whole budget otherwise."""
        if self.on_demand:
            return pages_needed(self._feed_tokens(request).shape[1],
                                self.page_size)
        return self._request_pages(request)

    def _request_pages(self, request: Request) -> int:
        """Total page reservation across arms: a speculative engine
        holds a second, same-length chain in the draft arm's KV format
        out of the shared allocator."""
        arms = 2 if self.draft is not None else 1
        return self._arm_pages(request) * arms

    def _shape_key(self, request: Request):
        """Padded-batch compile key: prefill-feed bucket (prompt, plus
        replayed tokens for a resumed request) + side-input shapes."""
        key = [self._bucket(self._feed_tokens(request).shape[1])]
        for k in ("src_tokens", "frames", "img_embeds"):
            if k in request.inputs:
                key.append((k, tuple(request.inputs[k].shape[1:])))
        return tuple(key)

    def _take_group(self) -> List[Request]:
        """Pop the next batched-prefill admission group off the queue.

        FIFO scan from the head: take same-shaped requests while slots
        and pages last, then trim to a power-of-two batch so compiled
        prefill shapes stay bounded. An empty return means the head
        request is blocked (no slot, or its page reservation cannot be
        met until in-flight requests retire) — admission never skips
        over it, so no request starves.
        """
        free = sum(not s.active for s in self.slots)
        if self.sla is not None:
            # SLA-tuned prefill group cap: smaller admission batches get
            # queued heads to their first token sooner when TTFT slips
            free = min(free, self.sla.prefill_cap)
        if not free or not self._queue:
            return []
        head_key = self._shape_key(self._queue[0])
        group: List[Request] = []
        need = 0
        for r in self._queue:
            if len(group) >= free or self._shape_key(r) != head_key:
                break
            pages = self._admit_pages(r)
            if not self.allocator.can_alloc(need + pages):
                break
            group.append(r)
            need += pages
        n = 1
        while n * 2 <= len(group):
            n *= 2
        group = group[:n]
        for _ in group:
            self._queue.popleft()
        return group

    def _group_inputs(self, toks, sides) -> dict:
        """Prefill inputs of one admission group: the feeds ``toks``
        right-padded to the longest one's bucket, their true lengths,
        and the side inputs (sources, frames, image embeddings) of the
        members' input dicts ``sides``, stacked."""
        pad_to = self._bucket(max(t.shape[1] for t in toks))
        inputs = {self._tkey: jnp.concatenate(
            [jnp.pad(t, ((0, 0), (0, pad_to - t.shape[1]))) for t in toks])}
        inputs["lengths"] = jnp.asarray([t.shape[1] for t in toks],
                                        jnp.int32)
        for k in ("src_tokens", "frames", "img_embeds"):
            if k in sides[0]:
                inputs[k] = jnp.concatenate([s[k] for s in sides])
        return inputs

    def _admit_group(self, group: List[Request]):
        """Admit a same-shape group under ONE jitted prefill+insert
        call. A resumed (previously preempted) request prefills its
        prompt + already-emitted tokens (minus the last, which becomes
        the pending decode token) — teacher-forced replay that rebuilds
        the exact KV/PRNG state it was evicted with, so its remaining
        stream is token-identical. Slot state for the WHOLE group goes
        live before any first-token callback fires, so a callback
        aborting a groupmate finds it admitted (and retirable) instead
        of racing a half-built group."""
        n = len(group)
        free = [s.id for s in self.slots if not s.active][:n]
        tr = self.trace
        t_adm = self._now()
        self._stamp_admitted(group, t_adm)
        if tr is not None:
            for r in group:
                tr.end(r.id + 1, "queued", t_adm)
            p0 = time.perf_counter()
        with self._span("admit.inputs", ADMIT_TID):
            toks = [self._feed_tokens(r) for r in group]
            true_lens = [t.shape[1] for t in toks]
            inputs = self._group_inputs(toks, [r.inputs for r in group])
            keys = jnp.stack(
                [jax.random.PRNGKey(r.params.seed) for r in group])
        with self._span("admit.pages", ADMIT_TID):
            chains = []
            rows = np.zeros((n, self.max_pages), np.int32)  # 0 = trash
            for i, r in enumerate(group):
                chain = self.allocator.alloc_chain(
                    pages_needed(true_lens[i], self.page_size)
                    if self.on_demand else self._arm_pages(r))
                chains.append(chain)
                rows[i, :len(chain)] = chain
            dchains = []
            if self.draft is not None:
                drows = np.zeros((n, self.max_pages), np.int32)
                for i, r in enumerate(group):
                    dchain = self.allocator.alloc_chain(self._arm_pages(r))
                    dchains.append(dchain)
                    drows[i, :len(dchain)] = dchain
        with self._span("admit.prefill", ADMIT_TID, group=n):
            self.cache, first = self._prefill_paged_fn(
                self.params, inputs, jnp.asarray(true_lens, jnp.int32),
                jnp.asarray(free, jnp.int32), jnp.asarray(rows), self.cache,
                jnp.asarray([r.params.temperature for r in group],
                            jnp.float32),
                jnp.asarray([r.params.top_k for r in group], jnp.int32),
                jnp.asarray([r.params.top_p for r in group], jnp.float32),
                keys)
            if self.draft is not None:
                self.draft_cache = self._draft_prefill_paged_fn(
                    self.draft.params, inputs,
                    jnp.asarray(true_lens, jnp.int32),
                    jnp.asarray(free, jnp.int32), jnp.asarray(drows),
                    self.draft_cache)
            self.prefill_shapes.add(
                tuple(sorted((k, tuple(v.shape))
                             for k, v in inputs.items())))
        with self._span("admit.first_sync", ADMIT_TID):
            first = np.asarray(first)
        now = self._now()
        if tr is not None:
            # one batched prefill covers the group; each member gets the
            # same complete event on its own track
            p_dur = time.perf_counter() - p0
            for r in group:
                tr.complete(r.id + 1, "prefill", now - p_dur, p_dur,
                            group=n)
        with self._span("admit.install", ADMIT_TID):
            admitted = []
            for i, (r, sid) in enumerate(zip(group, free)):
                s = self.slots[sid]
                sp = r.params
                stash = self._preempted.pop(r.id, None)
                if stash:
                    # resume: the replay prefill's sampled token is
                    # discarded — the pending decode token is the last
                    # one emitted before eviction, and the PRNG offset
                    # picks up at fold len(stash), exactly the
                    # pre-eviction state
                    tok = int(stash[-1])
                    self._resumed += 1
                    fid = self._flow_ids.pop(r.id, None)
                    if tr is not None:
                        tr.instant(r.id + 1, "resumed", now,
                                   replayed=len(stash))
                        if fid is not None:
                            tr.flow_end(r.id + 1, "resume", now, fid)
                else:
                    tok = int(first[i])
                self.cur = self.cur.at[sid, 0].set(tok)
                self._temps = self._temps.at[sid].set(sp.temperature)
                self._top_ks = self._top_ks.at[sid].set(sp.top_k)
                self._top_ps = self._top_ps.at[sid].set(sp.top_p)
                self._keys = self._keys.at[sid].set(keys[i])
                self._offsets = self._offsets.at[sid].set(
                    len(stash) if stash else 1)
                self._chains[r.id] = chains[i]
                if self.draft is not None:
                    self._draft_chains[r.id] = dchains[i]
                s.request = r
                s.tokens = list(stash) if stash else []
                s.active = True
                s.seq = self._admit_seq
                self._admit_seq += 1
                if self.on_demand:
                    self._disp_len[sid] = true_lens[i]
                self._last_admitted_slot = sid
                self._dirty_slots.add(sid)
                admitted.append((s, r, tok, stash is not None))
        # first-token delivery only after EVERY slot in the group is
        # live (see docstring); resumed requests already streamed their
        # stashed tokens before eviction and re-emit nothing
        with self._span("admit.emit", ADMIT_TID):
            for s, r, tok, resumed in admitted:
                if not s.active or s.request is not r:
                    continue    # a groupmate's callback aborted it
                if resumed:
                    continue
                self._stats[r.id].first_token_s = now
                self._emit(s, tok, synced=False)

    def _stamp_admitted(self, group: List[Request], now: float) -> None:
        """Close the queue wait of each request of an admission group
        (a first admission or a resume) in its RequestStats."""
        for r in group:
            st = self._stats[r.id]
            st.admitted_s = now
            st.queued_s += now - self._queued_at.pop(r.id)

    # -- dense admission -----------------------------------------------

    def _admit(self, request: Request):
        slot = self.free_slot()
        s = self.slots[slot]
        sp = request.params
        tr = self.trace
        t_adm = self._now()
        self._stamp_admitted([request], t_adm)
        if tr is not None:
            tr.end(request.id + 1, "queued", t_adm)
            p0 = time.perf_counter()
        inputs = dict(request.inputs)
        toks = inputs[self._tkey]
        true_len = toks.shape[1]
        if self._bucketed:
            pad_to = self._bucket(true_len)
            if pad_to > true_len:
                toks = jnp.pad(toks, ((0, 0), (0, pad_to - true_len)))
            inputs[self._tkey] = toks
            inputs["lengths"] = jnp.full((1,), true_len, jnp.int32)
        key = jax.random.PRNGKey(sp.seed)
        one_cache, tok = self._prefill_fn(
            self.params, inputs, jnp.int32(true_len),
            jnp.float32(sp.temperature), jnp.int32(sp.top_k),
            jnp.float32(sp.top_p), key)
        self.prefill_shapes.add(
            tuple(sorted((k, tuple(v.shape)) for k, v in inputs.items())))
        self.cache = self._splice(self.cache, self._pad_cross(one_cache),
                                  slot)
        if self.draft is not None:
            done = self._draft_prefill_fn(self.draft.params, inputs)
            self.draft_cache = self._splice(
                self.draft_cache, self._pad_cross(done), slot)
        tok = int(tok)
        if tr is not None:
            p_dur = time.perf_counter() - p0
            tr.complete(request.id + 1, "prefill", self._now() - p_dur,
                        p_dur)
        self.cur = self.cur.at[slot, 0].set(tok)
        self._temps = self._temps.at[slot].set(sp.temperature)
        self._top_ks = self._top_ks.at[slot].set(sp.top_k)
        self._top_ps = self._top_ps.at[slot].set(sp.top_p)
        self._keys = self._keys.at[slot].set(key)
        self._offsets = self._offsets.at[slot].set(1)  # token 0 drew fold 0
        s.request = request
        s.tokens = []                   # prefill produced the first token
        s.active = True
        s.seq = self._admit_seq
        self._admit_seq += 1
        self._last_admitted_slot = slot
        self._dirty_slots.add(slot)
        self._stats[request.id].first_token_s = self._now()
        self._emit(s, tok, synced=False)

    def _maybe_retire(self, s: _Slot):
        sp = s.request.params
        if sp.eos_id is not None and s.tokens[-1] == sp.eos_id:
            self._retire(s, "eos")
        elif len(s.tokens) >= sp.max_new_tokens:
            self._retire(s, "length")

    def _retire(self, s: _Slot, reason: str):
        rid = s.request.id
        st = self._stats.pop(rid)
        st.finished_s = self._now()
        st.new_tokens = len(s.tokens)
        out = RequestOutput(
            rid, s.request.inputs, list(s.tokens), reason, st, slot=s.id)
        self._finished.append(out)
        # every served retirement feeds the latency histograms (queued
        # requests that never reached a slot don't — see _finish_queued)
        self._ttft_hist.record(out.ttft_ms)
        self._tpot_hist.record(out.tpot_ms)
        if self.trace is not None:
            tid = rid + 1
            if reason in ("deadline", "error"):
                self.trace.instant(tid, reason, st.finished_s)
            self.trace.instant(tid, "retired", st.finished_s,
                               reason=reason, tokens=st.new_tokens)
            self.trace.end(tid, "request", st.finished_s)
        if reason == "deadline":
            self._deadline_expirations += 1
        elif reason == "error":
            self._slot_errors += 1
        if self.sla is not None and reason in ("eos", "length"):
            # only clean completions feed the percentile window: aborts
            # carry caller-truncated timings, and fault-path timings
            # (deadline / preempted_limit / error) would reward
            # load-shedding with a "better" p95
            self.sla.observe(out)
        self._preempted.pop(rid, None)
        self._preempt_counts.pop(rid, None)
        self._disp_len.pop(s.id, None)
        s.active = False
        s.request = None
        if self.paged:
            # reclaim the chain and park the slot on the trash page so
            # its idle decode writes cannot touch live pages; both arms'
            # chains are freed together, by this one path, whatever the
            # finish reason — a second free would raise in the allocator
            self.allocator.free_chain(self._chains.pop(rid))
            self.cache["block_tables"] = \
                self.cache["block_tables"].at[s.id].set(TRASH_PAGE)
            self.cache["active"] = self.cache["active"].at[s.id].set(0)
            self.cache["len"] = self.cache["len"].at[s.id].set(0)
            if self.draft is not None:
                self.allocator.free_chain(self._draft_chains.pop(rid))
                self.draft_cache["block_tables"] = \
                    self.draft_cache["block_tables"].at[s.id].set(TRASH_PAGE)
                self.draft_cache["active"] = \
                    self.draft_cache["active"].at[s.id].set(0)
                self.draft_cache["len"] = \
                    self.draft_cache["len"].at[s.id].set(0)

    def _pad_cross(self, one_cache):
        """Zero-pad a single-request cache's cross-attention leaves from
        the request's source length up to the engine's enc capacity so
        mixed source lengths splice into one batch cache (the valid span
        is tracked per slot via cross_len)."""
        if not self.enc_cap:
            return one_cache
        one_cache = dict(one_cache)
        for k, v in one_cache.items():
            if k.startswith("cross_") and v.ndim >= 3:
                se = v.shape[2]
                if se < self.enc_cap:
                    pad = [(0, 0)] * v.ndim
                    pad[2] = (0, self.enc_cap - se)
                    one_cache[k] = jnp.pad(v, pad)
        return one_cache

    _BATCH_LEADING = ("'pos'", "'len'", "'pos_roll'")

    def _splice(self, batch_cache, one_cache, slot: int):
        """Write a single-request cache into batch slot ``slot``.

        Batch axis position differs per leaf: 'pos'/'len'/'pos_roll' carry
        batch at dim 0; layer-stacked KV/state leaves carry it at dim 1.
        """
        def put(path, c, o):
            pstr = jax.tree_util.keystr(path)
            if c.ndim == 0:
                return c
            o = o.astype(c.dtype)   # e.g. f32 prefill state into bf16 cache
            if any(k in pstr for k in self._BATCH_LEADING) or c.ndim == 1:
                return c.at[slot].set(o[0])            # batch-leading leaf
            return c.at[:, slot].set(o[:, 0])          # layer-leading leaf
        return jax.tree_util.tree_map_with_path(put, batch_cache, one_cache)


# ---------------------------------------------------------------------------
# legacy one-shot wrappers (thin shims over a single-shot engine)
# ---------------------------------------------------------------------------

def _row(batch: dict, i: int) -> dict:
    return {k: v[i:i + 1] for k, v in batch.items()
            if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1}


_DEPRECATION = (
    " is deprecated and will be removed: deploy() a TranslationPipeline "
    "from repro.serving and use pipe.generate()/pipe.translate() — or the "
    "streaming surface (pipe.translate_stream / engine.submit(on_token=...)"
    " / engine.stream()) for token-at-a-time delivery")


def greedy_generate(model, ctx, params, batch, *, steps: int, max_len: int,
                    kv_dtype: str = "bf16", eos_id: Optional[int] = None):
    """Deprecated prefill + greedy decode shim; see ``_DEPRECATION``.

    Returns (tokens (B, steps), cache)."""
    warnings.warn("greedy_generate" + _DEPRECATION, DeprecationWarning,
                  stacklevel=2)
    return _greedy_generate(model, ctx, params, batch, steps=steps,
                            max_len=max_len, kv_dtype=kv_dtype,
                            eos_id=eos_id)


def _greedy_generate(model, ctx, params, batch, *, steps: int, max_len: int,
                     kv_dtype: str = "bf16", eos_id: Optional[int] = None):
    """Prefill + greedy decode. Returns (tokens (B, steps), cache).

    Thin wrapper over a single-shot ServeEngine (one slot per batch row).
    When ``eos_id`` is set, a sequence stops at its first EOS and the
    remaining positions are masked with ``eos_id`` (the returned shape
    stays (B, steps)); ``eos_id=None`` (default) never stops early.
    """
    tkey = "tgt_in" if model.cfg.family in ("encdec", "audio") else "tokens"
    B = batch[tkey].shape[0]
    eng = ServeEngine(model, params, slots=B, max_len=max_len,
                      kv_dtype=kv_dtype, ctx=ctx)
    sp = SamplingParams(max_new_tokens=steps, eos_id=eos_id)
    ids = [eng.submit(_row(batch, i), sp) for i in range(B)]
    outs = {o.request_id: o for o in eng.run_until_drained()}
    pad = 0 if eos_id is None else eos_id
    rows = [outs[r].token_ids + [pad] * (steps - len(outs[r].token_ids))
            for r in ids]
    return jnp.asarray(rows, jnp.int32), eng.cache


def translate(model, ctx, params, src_tokens, lang_code: int, *,
              steps: int, max_len: int = 0,
              kv_dtype: str = "bf16", eos_id: Optional[int] = None):
    """Deprecated NMT shim (paper Fig. 2b): many-to-many via target lang
    code; see ``_DEPRECATION`` — TranslationPipeline.translate /
    translate_stream is the supported surface.

    ``max_len`` defaults to the decoder prompt length (the 1-token lang
    code) + ``steps``; an explicit ``max_len`` too small for the request
    raises instead of silently wrapping the KV cache.
    """
    warnings.warn("translate" + _DEPRECATION, DeprecationWarning,
                  stacklevel=2)
    B = src_tokens.shape[0]
    prompt_len = 1                       # decoder prompt = target lang code
    max_len = max_len or prompt_len + steps
    if prompt_len + steps > max_len:
        raise ValueError(
            f"translate needs prompt_len + steps = {prompt_len} + {steps} "
            f"= {prompt_len + steps} cache positions but max_len={max_len}")
    tgt_in = jnp.full((B, 1), lang_code, jnp.int32)
    batch = {"src_tokens": src_tokens, "tgt_in": tgt_in}
    toks, _ = _greedy_generate(model, ctx, params, batch, steps=steps,
                               max_len=max_len, kv_dtype=kv_dtype,
                               eos_id=eos_id)
    return toks
