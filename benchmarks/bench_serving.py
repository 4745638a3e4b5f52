"""Serving throughput trajectory: the request-level engine under load.

The paper's headline deployment numbers (66 tok/s real-time NMT, 4.8x
throughput from quantization) are end-to-end *serving* figures, not bare
kernel times. This benchmark measures the deploy() pipeline the way
traffic hits it, at the bf16 / int8 / int4 presets on the reduced NLLB
config, along two axes the paged-KV engine moves:

  * dense vs paged at an EQUAL self-attention KV budget — the paged
    engine spends the same page pool across 2x the decode slots
    (requests reserve their actual prompt+decode budget, not the worst
    case), so burst traffic sees more concurrent decode lanes. For the
    enc-dec model benchmarked here the per-slot cross-attention cache
    still scales with slots, so total KV bytes are NOT equal — compare
    the kv_mb column, which reports the whole cache honestly;
  * tok/s vs request rate — requests arrive as seeded Poisson traffic
    (mean ``rate`` arrivals per scheduler round, injected through
    ``engine.stream(on_round=...)``) instead of as one burst,
    exercising continuous mid-flight admission through the overlapped
    scheduler. The seed is fixed, so CI trajectories compare identical
    arrival traces.

``--horizon K`` runs every engine with K-step horizon-fused decode (one
host sync per K decode steps instead of per token); rows then report
``decode_syncs`` and ``tokens_per_sync`` so the BENCH trajectory tracks
host-overhead elimination, and a tripwire reds the run if the fused
path silently fell back to per-token syncing (``decode_syncs`` above
``ceil(tokens/horizon) + slots``). At K > 1 every row also reports
``overlap_rounds`` — rounds whose host walk was hidden behind an
already-dispatched next scan — and a second tripwire reds the run when
a burst long enough to need several horizons per request never
overlapped once (the double-buffered loop silently degenerated to
dispatch-then-walk). ``--impl pallas`` routes matmuls through the
Pallas qmm kernel and paged attention through the Pallas block-table
kernel (on CPU set REPRO_PALLAS_INTERPRET=1).

``--sla-ttft-ms`` / ``--sla-tpot-ms`` add one serve_{policy}_sla row
per policy: the paged engine re-deployed with
``deploy(..., sla=SLATarget(...))``, served under the same Poisson
arrivals, reporting the measured p95s next to the targets, whether the
final observation window held them, how often the controller retuned,
and the horizon/prefill-cap it settled on.

``--faults`` adds one serve_{policy}_faults chaos row per policy: the
same burst is served twice on identically-configured tight-pool paged
engines — once fault-free (the reference), once under a fixed-seed
``FaultPlan`` injecting page-pool exhaustion (forcing preemption +
resume), NaN logits (forcing a slot error), and deadline-clock skew
against one deadline-carrying request. The row reports the engine's
fault counters plus ``survivor_diffs`` (surviving requests whose token
streams differ from the reference — the fault-isolation claim) and
``prefix_violations`` (failed requests whose partial tokens are not a
prefix of their reference stream). Tripwires red the run unless
survivor_diffs == 0, every planned fault class actually fired
(preemptions, slot errors, deadline expirations all > 0), and the page
allocator's invariant holds after the drain.

``--spec-decode SPEC`` additionally measures each policy with a
speculative draft arm (the same checkpoint quantized at SPEC drafts
``LOOKAHEAD`` tokens per verify round; greedy output is unchanged).
Spec rows report acceptance rate, mean accepted tokens per verify
round, and verify calls per generated token; two tripwires red the run
if the draft arm is dead weight — acceptance must be > 0 and the spec
arm must need FEWER target-model forwards than the target-only run of
the same burst (``verify_calls`` below the baseline's decode steps;
run at --horizon 1 for an exact dispatch-level comparison).

``--trace`` adds one serve_{policy}_traced observability row per
policy: the same burst is served twice on identically-configured paged
engines — untraced reference, then with
``deploy(..., trace=TraceConfig())`` — and tripwires red the run
unless the tracer behaved as a pure observer: traced token streams and
``decode_syncs`` exactly equal the reference's, the trace carries one
CLOSED request span per submitted request (no warmup pass, so the
counts line up), the span/phase stack passes ``Tracer.check()`` with
zero ring drops, the four round-phase timers (admit / dispatch / sync
/ walk) sum to more than zero and at most the measured wall clock, and
the export parses as Chrome/Perfetto trace_event JSON.
``--trace-out`` / ``--metrics-out`` (each implies ``--trace``) write
the traced arm's Perfetto JSON and Prometheus text exposition as CI
artifacts.

``--mesh dp<N>,tp<K>`` adds one serve_{policy}_dp{N}_tp{K} cluster row
per policy (``repro.cluster``): the burst is served through a
``ReplicaRouter`` over N replicas (each tensor-parallel over its own
K-device ``("model",)`` mesh — on CPU force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) next to a
single engine configured exactly like one replica serving one
replica's share of the burst (equal per-engine work, same collectives
— the honest scale-out baseline on any core count). The row reports
cluster tok/s next to that single-replica baseline, per-replica
occupancy, and merged-histogram p95s (``Histogram.merge`` across
replicas — never averaged percentiles). Tripwires red the run unless
the routed token streams exactly match a full-burst single-engine
reference, the merged histogram count equals the sum of the
per-replica counts, and the router's aggregate throughput holds the
single-replica baseline (full serialization already ties it, so
falling 15% below means the routing layer itself burns the time).

Rows (CSV on stdout; ``--json PATH`` additionally writes the artifact
consumed by CI's bench-smoke job):
  serve_{policy}_{dense|paged}   burst throughput + occupancy + kv MB
  serve_{policy}_paged_rate{r}   Poisson continuous-arrival throughput
  serve_{policy}_{mode}_specdec  speculative-decoding arm (--spec-decode)
  serve_{policy}_sla             SLA-admission arm (--sla-ttft-ms/...)
  serve_{policy}_faults          fault-injection chaos arm (--faults)
  serve_{policy}_traced          observability arm (--trace)
  serve_{policy}_dp{N}_tp{K}     replica-router cluster arm (--mesh)
Every serving row also records per-request latency percentiles
(p50/p95 TTFT and per-output-token time, from RequestStats via the
latency_percentiles helper the eval suite shares).

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke] [--json P]
        [--horizon K] [--rate R] [--impl xla|pallas] [--faults]
        [--trace] [--trace-out P] [--metrics-out P] [--mesh dp2,tp2]
        [--spec-decode w4a8kv8] [--sla-ttft-ms T --sla-tpot-ms T]
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax.numpy as jnp
import numpy as np

from repro.cluster import deploy_replicas, parse_mesh_spec, tp_mesh
from repro.configs import get_config, reduce_config
from repro.core import resolve_spec
from repro.data import SyntheticTranslation
from repro.obs import PHASES
from repro.runtime import configure_compile_cache
from repro.serving import (IMPL_CHOICES, FaultPlan, SamplingParams,
                           SLATarget, TraceConfig, deploy, impl_routes,
                           latency_percentiles, pages_needed)

from .common import csv_row

POLICIES = ("bf16", "int8", "int4")
REQUESTS = 8
GEN = 8
SLOTS = 4
MAX_LEN = 32
PAGE = 4
LOOKAHEAD = 4       # draft tokens per verify round (--spec-decode arm)


def _requests(cfg, n):
    ds = SyntheticTranslation(cfg.vocab_size, cfg.enc_len, seed=0)
    reqs = []
    for _ in range(n):
        b = ds.sample(1)
        reqs.append({"src_tokens": jnp.asarray(b["src_tokens"]),
                     "tgt_in": jnp.asarray(b["tgt_in"][:, :1])})
    return reqs


def serve_burst(eng, reqs, gen):
    """All requests at t=0; returns (tokens, seconds, occupancy, outputs)."""
    sp = SamplingParams(max_new_tokens=gen)
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r, sp)
    outs = eng.run_until_drained()
    dt = time.perf_counter() - t0
    return sum(o.num_generated for o in outs), dt, eng.occupancy, outs


def serve_rate(eng, reqs, gen, rate, seed=0):
    """Poisson arrivals (mean ``rate`` per scheduler round, seeded rng)
    injected through the overlapped streaming loop. A drained engine
    with arrivals still pending is force-fed one request so the stream
    never exits early on an unlucky run of zero draws."""
    sp = SamplingParams(max_new_tokens=gen)
    pending = list(reqs)
    rng = np.random.default_rng(seed)

    def arrive():
        if not pending:
            return
        n = int(rng.poisson(rate))
        if n == 0 and eng.num_active == 0 and eng.num_pending == 0:
            n = 1
        for r in pending[:n]:
            eng.submit(r, sp)
        del pending[:n]

    t0 = time.perf_counter()
    outs = []
    arrive()
    while pending or len(outs) < len(reqs):
        outs.extend(eng.stream(on_round=arrive))
    dt = time.perf_counter() - t0
    return sum(o.num_generated for o in outs), dt, eng.occupancy, outs


FAULT_SLOTS = 4
FAULT_PAGES = 12    # == FAULT_SLOTS full chains: zero slack once stolen


def _fault_plan():
    """The fixed chaos schedule the --faults arm injects (one instance
    per engine — plans are stateful). Exhaustion at round 1 grabs the
    pool's whole slack before the first wave's chains finish growing —
    at any horizon — and holds it long enough that growth must preempt
    (the round-6 steal stresses the second admission wave the same
    way); the NaN poisons slot 0 at decode dispatch 1 (slot 0 holds the
    oldest in-flight request, which preemption never victimizes, so the
    slot is guaranteed live and the poison guaranteed to register as a
    slot error), and the round-4 clock skew expires the one
    deadline-carrying request. All coordinates are explicit, so the
    fault counts CI tripwires on are guaranteed, not probabilistic."""
    return FaultPlan(seed=0,
                     exhaust_at=[(1, 6, 4), (6, 6, 3)],
                     nan_at=[(1, 0, 0)],
                     skew_at=[(4, 60_000.0)])


def serve_faults(pol, reqs, gen, horizon, impl):
    """Serve one burst twice — fault-free, then under _fault_plan() on
    an identical engine — and compare streams request-by-request.
    Returns (row dict, tripwire list)."""
    sp = SamplingParams(max_new_tokens=gen)
    # the last request carries a deadline; the round-4 skew expires it
    dl_sp = SamplingParams(max_new_tokens=gen, deadline_ms=500.0)

    def burst(plan):
        pipe = deploy("nllb600m", pol, slots=FAULT_SLOTS, max_len=MAX_LEN,
                      smoke=True, paged=True, page_size=PAGE,
                      num_pages=FAULT_PAGES, horizon=horizon, faults=plan,
                      **impl_routes(impl))
        ids = []
        for i, r in enumerate(reqs):
            p = dl_sp if (plan is not None and i == len(reqs) - 1) else sp
            ids.append(pipe.engine.submit(r, p))
        t0 = time.perf_counter()
        outs = {o.request_id: o for o in pipe.engine.run_until_drained()}
        dt = time.perf_counter() - t0
        if plan is not None:
            plan.release_all(pipe.engine)
        pipe.engine.allocator.check()
        return [outs[i] for i in ids], dt, pipe.engine

    ref, _, _ = burst(None)
    plan = _fault_plan()
    outs, dt, eng = burst(plan)

    survivor_diffs = prefix_violations = 0
    for o, r in zip(outs, ref):
        if o.finish_reason in ("eos", "length"):
            survivor_diffs += int(o.token_ids != r.token_ids)
        else:
            prefix_violations += int(
                o.token_ids != r.token_ids[:len(o.token_ids)])
    m = eng.metrics()
    toks = sum(o.num_generated for o in outs)
    name = f"serve_{pol}_faults"
    row = {
        "tok_s": round(toks / dt, 1),
        "requests": len(reqs),
        "horizon": horizon,
        "faults_injected": len(plan.events),
        "preemptions": m.preemptions,
        "resumed": m.resumed_requests,
        "deadline_expirations": m.deadline_expirations,
        "slot_errors": m.slot_errors,
        "admission_rejections": m.admission_rejections,
        "survivor_diffs": survivor_diffs,
        "prefix_violations": prefix_violations,
        "pages_in_use_after_drain": eng.allocator.pages_in_use,
    }
    tripped = []
    if survivor_diffs:
        tripped.append(f"{name}: {survivor_diffs} surviving requests "
                       "diverged from the fault-free reference")
    if prefix_violations:
        tripped.append(f"{name}: {prefix_violations} failed requests' "
                       "partial tokens are not a reference prefix")
    for counter in ("preemptions", "slot_errors", "deadline_expirations"):
        if not row[counter]:
            tripped.append(f"{name}: planned fault class never fired "
                           f"({counter} == 0)")
    if eng.allocator.pages_in_use:
        tripped.append(f"{name}: {eng.allocator.pages_in_use} pages leaked "
                       "after drain")
    return name, dt, toks, row, tripped


def serve_traced(pol, reqs, gen, horizon, impl,
                 trace_out=None, metrics_out=None):
    """Serve one burst twice on identically-configured paged engines —
    untraced reference, then with ``deploy(..., trace=TraceConfig())``
    — and hold the tracer to its observer contract. No warmup pass:
    every request lands in a fresh engine, so the trace must carry
    exactly one closed, stack-discipline-clean request span per
    request with zero ring drops; and the traced engine's token
    streams and ``decode_syncs`` must equal the untraced reference's
    exactly (tracing must not add host syncs or change scheduling).
    Returns (name, dt, toks, row, tripwires)."""
    def burst(trace):
        pipe = _deploy(pol, True, SLOTS, smoke=True, horizon=horizon,
                       impl=impl, trace=trace)
        toks, dt, _, outs = serve_burst(pipe.engine, reqs, gen)
        return toks, dt, sorted(outs, key=lambda o: o.request_id), pipe

    _, _, ref, ref_pipe = burst(None)
    toks, dt, outs, pipe = burst(TraceConfig())

    tr = pipe.tracer
    problems = tr.check()
    spans = tr.request_spans()
    closed = sum(1 for s in spans.values() if s["closed"])
    m = pipe.engine.metrics()
    phase_ms = {p: getattr(m, f"phase_{p}_ms") for p in PHASES}
    phase_sum = sum(phase_ms.values())
    wall_ms = dt * 1e3
    streams_match = all(o.token_ids == r.token_ids
                        for o, r in zip(outs, ref))
    syncs, ref_syncs = pipe.engine.decode_syncs, ref_pipe.engine.decode_syncs

    name = f"serve_{pol}_traced"
    row = {
        "tok_s": round(toks / dt, 1),
        "requests": len(reqs),
        "horizon": horizon,
        "events": len(tr),
        "dropped": tr.dropped,
        "spans": len(spans),
        "spans_closed": closed,
        "check_problems": len(problems),
        "streams_match": int(streams_match),
        "decode_syncs": syncs,
        "decode_syncs_ref": ref_syncs,
        **{f"phase_{p}_ms": round(v, 3) for p, v in phase_ms.items()},
        "phase_sum_ms": round(phase_sum, 3),
        "wall_ms": round(wall_ms, 3),
        **latency_percentiles(outs),
    }
    tripped = []
    if not streams_match:
        tripped.append(f"{name}: traced token streams diverged from the "
                       "untraced reference — the tracer is not an observer")
    if syncs != ref_syncs:
        tripped.append(f"{name}: traced decode_syncs {syncs} != untraced "
                       f"{ref_syncs} — tracing added host syncs")
    if len(spans) != len(reqs):
        tripped.append(f"{name}: {len(spans)} request spans != "
                       f"{len(reqs)} requests")
    if closed != len(spans):
        tripped.append(f"{name}: {len(spans) - closed} request spans "
                       "never closed")
    if problems:
        tripped.append(f"{name}: trace discipline: "
                       + "; ".join(problems[:3]))
    if tr.dropped:
        tripped.append(f"{name}: ring buffer dropped {tr.dropped} events "
                       "on a burst this small")
    if not 0.0 < phase_sum <= wall_ms * 1.05:
        tripped.append(f"{name}: phase sum {phase_sum:.1f} ms outside "
                       f"(0, {wall_ms:.1f} * 1.05] ms wall — phase timers "
                       "are not measuring disjoint slices of the run")
    try:
        chrome = json.loads(json.dumps(tr.to_chrome()))
        if not isinstance(chrome.get("traceEvents"), list):
            raise ValueError("no traceEvents list")
    except (TypeError, ValueError) as exc:
        tripped.append(f"{name}: trace is not valid Chrome JSON ({exc})")
    if trace_out:
        tr.dump_json(trace_out)
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(pipe.engine.prometheus())
    return name, dt, toks, row, tripped


def serve_mesh(pol, reqs, gen, horizon, impl, dp, tp):
    """Serve the burst through a ReplicaRouter over ``dp`` replicas
    (each tensor-parallel over its own ``tp``-device mesh) next to a
    single engine configured exactly like one replica serving one
    replica's SHARE of the burst, and hold the cluster to its
    contract: routed token streams identical to the single engine's,
    merged histograms that account for every per-replica sample, and
    aggregate throughput at least the single replica's — per-engine
    work is identical on both sides, so even a router that fully
    serializes its replicas only ties the baseline, and any
    cross-replica overlap pushes it above; falling meaningfully below
    means the routing layer itself burns the time. Returns
    (name, dt, toks, row, tripwires)."""
    sp = SamplingParams(max_new_tokens=gen)
    # every slot can hold a full chain: deterministic, preemption-free
    pages = SLOTS * pages_needed(MAX_LEN, PAGE)
    kwargs = dict(slots=SLOTS, max_len=MAX_LEN, smoke=True,
                  paged=True, page_size=PAGE, num_pages=pages,
                  horizon=horizon, **impl_routes(impl))

    def burst(eng, rs):
        for r in rs:
            eng.submit(r, sp)
        t0 = time.perf_counter()
        outs = eng.run_until_drained()
        return (sum(o.num_generated for o in outs),
                time.perf_counter() - t0,
                sorted(outs, key=lambda o: o.request_id))

    single = deploy("nllb600m", pol,
                    mesh=tp_mesh(tp) if tp > 1 else None, **kwargs)
    # full burst once: compiles + the stream-equivalence reference
    _, _, ref = burst(single.engine, reqs)
    single.engine.reset_metrics()
    # timed baseline: one replica serving one replica's share
    share = reqs[:max(1, len(reqs) // dp)]

    cluster = deploy_replicas("nllb600m", pol, replicas=dp, tp=tp, **kwargs)
    router = cluster.engine
    burst(router, reqs)                              # warmup: compiles
    router.reset_metrics()

    # alternate A/B repeats and compare best-of-n floors: shared CI
    # boxes jitter 2-3x run to run, and a noisy phase long enough to
    # cover consecutive runs would bias back-to-back arms — pairing
    # the draws spreads it over both (streams are identical anyway)
    ref_runs, runs = [], []
    for _ in range(3):
        ref_runs.append(burst(single.engine, share))
        runs.append(burst(router, reqs))
    ref_toks, ref_dt, _ = min(ref_runs, key=lambda r: r[1])
    toks, dt, outs = min(runs, key=lambda r: r[1])

    m = router.metrics()
    merged = router.merged_latency_histograms()
    per = [e.latency_histograms() for e in router.replicas]
    merged_counts = {k: h.count for k, h in merged.items()}
    summed_counts = {k: sum(p[k].count for p in per) for k in merged}
    tok_s, ref_tok_s = toks / dt, ref_toks / ref_dt

    name = f"serve_{pol}_dp{dp}_tp{tp}"
    row = {
        "tok_s": round(tok_s, 1),
        "single_tok_s": round(ref_tok_s, 1),
        "requests": len(reqs),
        "dp": dp, "tp": tp, "horizon": horizon,
        **{f"occupancy_r{i}": round(e.occupancy, 3)
           for i, e in enumerate(router.replicas)},
        "ttft_p95_ms": m.ttft_p95_ms,     # from Histogram.merge, not
        "tpot_p95_ms": m.tpot_p95_ms,     # averaged per-replica p95s
        "merged_ttft_count": merged_counts["ttft_ms"],
        "merged_tpot_count": merged_counts["tpot_ms"],
        "preemptions": m.preemptions,
    }
    tripped = []
    streams_match = all(
        o.token_ids == r.token_ids and o.finish_reason == r.finish_reason
        for o, r in zip(outs, ref))
    if len(outs) != len(ref) or not streams_match:
        tripped.append(f"{name}: routed token streams diverged from the "
                       "single-engine reference")
    if merged_counts != summed_counts:
        tripped.append(f"{name}: merged histogram counts {merged_counts} "
                       f"!= per-replica sums {summed_counts}")
    if tok_s < ref_tok_s * 0.85:
        # per-engine work is identical on both sides (each serves one
        # share), so full serialization already ties the baseline and
        # any cross-replica overlap wins; the 15% guard absorbs what
        # best-of-3 timing floors still jitter on shared CI runners
        tripped.append(
            f"{name}: router {tok_s:.1f} tok/s fell below the "
            f"single-replica baseline {ref_tok_s:.1f} tok/s")
    return name, dt, toks, row, tripped


def _deploy(pol, paged, slots, smoke, horizon=1, impl="xla", draft=None,
            sla=None, trace=None):
    # paged engine: same page pool as the dense engine's KV capacity,
    # spread over twice the slots — memory buys concurrency, not padding
    impls = impl_routes(impl)
    if draft is not None:
        impls.update(draft_spec=draft, draft_lookahead=LOOKAHEAD)
    if sla is not None:
        impls.update(sla=sla)
    if trace is not None:
        impls.update(trace=trace)
    if paged:
        pages = slots * pages_needed(MAX_LEN, PAGE)
        return deploy("nllb600m", pol, slots=2 * slots, max_len=MAX_LEN,
                      smoke=smoke, paged=True, page_size=PAGE,
                      num_pages=pages * (2 if draft else 1),
                      horizon=horizon, **impls)
    return deploy("nllb600m", pol, slots=slots, max_len=MAX_LEN, smoke=smoke,
                  horizon=horizon, **impls)


def _sync_bound(toks: int, horizon: int, extra: int) -> int:
    """Most decode syncs a healthy fused engine may need: one per full
    horizon of tokens plus ``extra`` partially-filled horizons — one
    per slot under burst admission (requests retire in waves), one per
    request under trickle admission (each admission lands at its own
    horizon boundary and can finish inside its own clamped scan)."""
    return math.ceil(toks / max(horizon, 1)) + extra


def run(smoke: bool = False, json_path: str | None = None,
        horizon: int = 1, impl: str = "xla",
        policies: list[str] | None = None,
        spec_decode: str | None = None,
        rate: int | None = None,
        sla_ttft_ms: float | None = None,
        sla_tpot_ms: float | None = None,
        faults: bool = False,
        trace: bool = False,
        trace_out: str | None = None,
        metrics_out: str | None = None,
        mesh: str | None = None):
    trace = trace or bool(trace_out) or bool(metrics_out)
    dp = tp = 1
    if mesh is not None:
        dp, tp = parse_mesh_spec(mesh)
        import jax
        need = dp * tp
        if len(jax.devices()) < need:
            raise RuntimeError(
                f"--mesh {mesh} needs {need} devices, have "
                f"{len(jax.devices())} (on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need})")
    if policies is None:
        policies = list(POLICIES[:2] if smoke else POLICIES)
    for pol in policies:                 # fail on typos before any build
        resolve_spec(pol)
    if spec_decode is not None:
        resolve_spec(spec_decode)
    sla = (SLATarget(p95_ttft_ms=sla_ttft_ms, p95_tpot_ms=sla_tpot_ms,
                     window=REQUESTS)
           if (sla_ttft_ms is not None or sla_tpot_ms is not None) else None)
    rates = [rate] if rate is not None else ([2] if smoke else [1, 2, 4])
    n_req = REQUESTS
    rows = []
    tripped = []

    def emit(name, us, derived: dict):
        txt = ";".join(f"{k}={v}" for k, v in derived.items())
        csv_row(name, us, txt)
        rows.append({"name": name, "us_per_call": round(us, 1),
                     "derived": derived})

    def check_syncs(name, eng, toks, extra):
        # silent-fallback tripwire: a fused engine that still syncs per
        # token reports ~toks syncs, far above the horizon-level bound
        bound = _sync_bound(toks, horizon, extra)
        if eng.decode_syncs > bound:
            tripped.append(
                f"{name}: decode_syncs {eng.decode_syncs} > "
                f"ceil({toks}/{horizon}) + {extra} = {bound}")

    def check_overlap(name, eng):
        # overlap tripwire: a run whose requests each span several
        # horizons must have dispatched ahead at least once — zero
        # means the double-buffered loop silently fell back to serial
        # dispatch-then-walk (spec-decode arms disable overlap by
        # design and are never checked here)
        if 1 < horizon < GEN - 1 and eng.metrics().overlap_rounds == 0:
            tripped.append(
                f"{name}: overlap_rounds == 0 at horizon {horizon} with "
                f"{GEN}-token requests — host walks are not being hidden "
                "behind dispatched-ahead scans")

    for pol in policies:
        occ = {}
        base_steps = {}
        for mode in ("dense", "paged"):
            pipe = _deploy(pol, mode == "paged", SLOTS, smoke=True,
                           horizon=horizon, impl=impl)
            reqs = _requests(pipe.cfg, n_req)
            serve_burst(pipe.engine, reqs, GEN)          # warmup: compiles
            pipe.engine.reset_metrics()                  # measured run only
            toks, dt, _, outs = serve_burst(pipe.engine, reqs, GEN)
            m = pipe.engine.metrics()
            occ[mode] = m.occupancy
            base_steps[mode] = m.decode_steps
            check_syncs(f"serve_{pol}_{mode}", pipe.engine, toks,
                        pipe.engine.n_slots)
            check_overlap(f"serve_{pol}_{mode}", pipe.engine)
            emit(f"serve_{pol}_{mode}", dt * 1e6 / max(toks, 1), {
                "tok_s": round(toks / dt, 1),
                "requests": n_req,
                "occupancy": round(m.occupancy, 3),
                "page_util": round(m.page_utilization, 3),
                "kv_mb": round(m.kv_cache_bytes / 2**20, 3),
                "compression": f"{pipe.compression:.2f}x",
                "prefill_compiles": m.prefill_compiles,
                "horizon": horizon,
                "decode_syncs": m.decode_syncs,
                "tokens_per_sync": round(m.mean_tokens_per_sync, 2),
                "overlap_rounds": m.overlap_rounds,
                **latency_percentiles(outs),
            })
            if spec_decode is None:
                continue
            # speculative arm: same checkpoint, same burst — the draft
            # quantized at --spec-decode proposes LOOKAHEAD tokens per
            # round, the target verifies them in one batched forward
            pipe = _deploy(pol, mode == "paged", SLOTS, smoke=True,
                           horizon=horizon, impl=impl, draft=spec_decode)
            reqs = _requests(pipe.cfg, n_req)
            serve_burst(pipe.engine, reqs, GEN)          # warmup: compiles
            pipe.engine.reset_metrics()                  # measured run only
            toks, dt, _, outs = serve_burst(pipe.engine, reqs, GEN)
            sm = pipe.engine.metrics()
            name = f"serve_{pol}_{mode}_specdec"
            emit(name, dt * 1e6 / max(toks, 1), {
                "tok_s": round(toks / dt, 1),
                "requests": n_req,
                "draft_spec": pipe.draft_spec_str,
                "lookahead": LOOKAHEAD,
                "acceptance_rate": round(sm.acceptance_rate, 4),
                "mean_accepted_per_verify":
                    round(sm.mean_accepted_per_verify, 3),
                "verify_calls": sm.verify_calls,
                "verify_per_token": round(sm.verify_calls / max(toks, 1), 4),
                "target_fw_baseline": base_steps[mode],
                "drafted": sm.drafted_tokens,
                "accepted": sm.accepted_tokens,
                **latency_percentiles(outs),
            })
            # tripwires: a draft arm that never agrees with the target,
            # or that costs MORE target forwards than decoding without
            # it, is dead weight — red the run (after the JSON artifact)
            if not sm.acceptance_rate > 0:
                tripped.append(f"{name}: acceptance_rate "
                               f"{sm.acceptance_rate:.4f} is not > 0")
            if sm.verify_calls >= base_steps[mode]:
                tripped.append(
                    f"{name}: verify_calls {sm.verify_calls} >= "
                    f"target-only decode steps {base_steps[mode]} — "
                    "speculation saved no target forwards")
        # acceptance tripwire: continuous paged admission must keep the
        # engine at least as busy as the dense baseline — a violation
        # reds the bench-smoke CI job (raised after the JSON artifact is
        # written so it still carries the numbers)
        ok = occ["paged"] >= occ["dense"] - 1e-9
        emit(f"serve_{pol}_occupancy_check", 0.0, {
            "paged": round(occ["paged"], 3), "dense": round(occ["dense"], 3),
            "paged_ge_dense": int(ok)})
        if not ok:
            tripped.append(
                f"{pol}: paged occupancy {occ['paged']:.3f} < dense "
                f"{occ['dense']:.3f}")

        for r in rates:
            pipe = _deploy(pol, True, SLOTS, smoke=True, horizon=horizon,
                           impl=impl)
            reqs = _requests(pipe.cfg, n_req)
            serve_rate(pipe.engine, reqs, GEN, r)        # warmup
            pipe.engine.reset_metrics()                  # measured run only
            toks, dt, occ_r, outs = serve_rate(pipe.engine, reqs, GEN, r)
            m = pipe.engine.metrics()
            check_syncs(f"serve_{pol}_paged_rate{r}", pipe.engine, toks,
                        n_req)
            emit(f"serve_{pol}_paged_rate{r}", dt * 1e6 / max(toks, 1), {
                "tok_s": round(toks / dt, 1), "rate_per_round": r,
                "occupancy": round(occ_r, 3),
                "decode_syncs": m.decode_syncs,
                "tokens_per_sync": round(m.mean_tokens_per_sync, 2),
                "overlap_rounds": m.overlap_rounds,
                **latency_percentiles(outs)})

        if faults:
            # chaos arm: fault-injected burst vs fault-free reference on
            # identical engines — stream equivalence is the product here,
            # so no warmup pass (timing is reported but not compared)
            fault_cfg = reduce_config(get_config("nllb600m"))
            fname, fdt, ftoks, frow, ftripped = serve_faults(
                pol, _requests(fault_cfg, n_req), GEN, horizon, impl)
            emit(fname, fdt * 1e6 / max(ftoks, 1), frow)
            tripped.extend(ftripped)

        if trace:
            # observability arm: traced burst vs untraced reference on
            # identical engines — observer equivalence is the product,
            # so no warmup pass (span count must equal request count);
            # trace/metrics artifacts come from the LAST traced policy
            trace_cfg = reduce_config(get_config("nllb600m"))
            tname, tdt, ttoks, trow, ttripped = serve_traced(
                pol, _requests(trace_cfg, n_req), GEN, horizon, impl,
                trace_out=trace_out, metrics_out=metrics_out)
            emit(tname, tdt * 1e6 / max(ttoks, 1), trow)
            tripped.extend(ttripped)

        if mesh is not None:
            # cluster arm: single-replica baseline vs ReplicaRouter over
            # dp replicas x tp-device meshes — stream equivalence and
            # merged-histogram accounting are the product (both runs get
            # their own warmup; see serve_mesh for the tripwires)
            mesh_cfg = reduce_config(get_config("nllb600m"))
            mname, mdt, mtoks, mrow, mtripped = serve_mesh(
                pol, _requests(mesh_cfg, n_req), GEN, horizon, impl, dp, tp)
            emit(mname, mdt * 1e6 / max(mtoks, 1), mrow)
            tripped.extend(mtripped)

        if sla is not None:
            # SLA-admission arm: same Poisson traffic, the engine's own
            # controller retunes horizon/prefill admission against the
            # measured percentiles (no sync-count tripwire here — the
            # controller changes the horizon mid-run by design)
            r = rates[0]
            pipe = _deploy(pol, True, SLOTS, smoke=True, horizon=horizon,
                           impl=impl, sla=sla)
            reqs = _requests(pipe.cfg, n_req)
            serve_rate(pipe.engine, reqs, GEN, r)        # warmup
            pipe.engine.reset_metrics()                  # measured run only
            toks, dt, _, outs = serve_rate(pipe.engine, reqs, GEN, r)
            m = pipe.engine.metrics()
            ctl = pipe.engine.sla
            lat = latency_percentiles(outs)
            held = ctl.holding()
            name = f"serve_{pol}_sla"
            emit(name, dt * 1e6 / max(toks, 1), {
                "tok_s": round(toks / dt, 1), "rate_per_round": r,
                "sla_ttft_ms": sla_ttft_ms, "sla_tpot_ms": sla_tpot_ms,
                "sla_held": None if held is None else int(held),
                "retunes": ctl.retunes,
                "final_horizon": ctl.horizon,
                "final_prefill_cap": ctl.prefill_cap,
                "overlap_rounds": m.overlap_rounds,
                **lat})
            if held is False:
                tripped.append(
                    f"{name}: final window missed the SLA "
                    f"(ttft_p95 {lat['ttft_p95_ms']}ms vs "
                    f"{sla_ttft_ms}, tpot_p95 {lat['tpot_p95_ms']}ms "
                    f"vs {sla_tpot_ms})")

    if json_path:
        with open(json_path, "w") as f:
            json.dump({"benchmark": "bench_serving", "smoke": smoke,
                       "horizon": horizon, "impl": impl,
                       "rate": rate, "sla_ttft_ms": sla_ttft_ms,
                       "sla_tpot_ms": sla_tpot_ms,
                       "spec_decode": spec_decode, "faults": faults,
                       "trace": trace, "mesh": mesh, "rows": rows},
                      f, indent=2)
    if tripped:
        raise RuntimeError("serving tripwire: " + "; ".join(tripped))
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep for CI perf-trajectory tracking")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    ap.add_argument("--horizon", type=int, default=1, metavar="K",
                    help="decode steps fused per host sync (1 = per-token)")
    ap.add_argument("--impl", choices=IMPL_CHOICES, default="xla",
                    help="kernel route: pallas = Pallas qmm matmuls + "
                         "Pallas paged attention (CPU runs need "
                         "REPRO_PALLAS_INTERPRET=1)")
    ap.add_argument("--policies", default=None, metavar="SPECS",
                    help="comma list of quantization specs (aliases or "
                         "grammar strings, e.g. bf16,w4a8kv8); default: "
                         "the standard preset sweep")
    ap.add_argument("--spec-decode", default=None, metavar="SPEC",
                    help="also measure each policy with a speculative "
                         "draft arm quantized at SPEC (e.g. w4a8kv8); "
                         "adds serve_*_specdec rows with acceptance "
                         "rate and verify-calls-per-token")
    ap.add_argument("--rate", type=int, default=None, metavar="R",
                    help="mean Poisson arrivals per scheduler round for "
                         "the continuous-admission rows (default: the "
                         "standard 1/2/4 sweep, 2 under --smoke)")
    ap.add_argument("--sla-ttft-ms", type=float, default=None, metavar="T",
                    help="p95 TTFT target: adds serve_*_sla rows served "
                         "under deploy(sla=SLATarget(...)) admission "
                         "control; a final window that misses the "
                         "target reds the run")
    ap.add_argument("--sla-tpot-ms", type=float, default=None, metavar="T",
                    help="p95 per-output-token target (see --sla-ttft-ms)")
    ap.add_argument("--faults", action="store_true",
                    help="add serve_*_faults chaos rows: the burst is "
                         "re-served under a fixed-seed FaultPlan (page "
                         "exhaustion, NaN logits, clock skew) and the "
                         "run reds unless survivors match the fault-free "
                         "reference and every fault class fired")
    ap.add_argument("--trace", action="store_true",
                    help="add serve_*_traced observability rows: the "
                         "burst is re-served with lifecycle tracing on "
                         "and the run reds unless the trace carries one "
                         "closed span per request, phase times sum to "
                         "at most the wall clock, and the traced token "
                         "streams + decode_syncs exactly match an "
                         "untraced reference")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the traced arm's Chrome/Perfetto "
                         "trace_event JSON here (implies --trace)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the traced arm's Prometheus text "
                         "exposition here (implies --trace)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="add serve_*_dp{N}_tp{K} cluster rows: the "
                         "burst re-served through a ReplicaRouter over "
                         "N replicas x K-device tensor-parallel meshes "
                         "(e.g. dp2,tp2; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8); "
                         "reds the run on stream divergence, histogram "
                         "miscounts, or throughput below the "
                         "single-replica baseline")
    args = ap.parse_args()
    configure_compile_cache()
    pols = ([p.strip() for p in args.policies.split(",") if p.strip()]
            if args.policies else None)
    run(smoke=args.smoke, json_path=args.json, horizon=args.horizon,
        impl=args.impl, policies=pols, spec_decode=args.spec_decode,
        rate=args.rate, sla_ttft_ms=args.sla_ttft_ms,
        sla_tpot_ms=args.sla_tpot_ms, faults=args.faults,
        trace=args.trace, trace_out=args.trace_out,
        metrics_out=args.metrics_out, mesh=args.mesh)


if __name__ == "__main__":
    main()
