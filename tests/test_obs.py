"""Observability: lifecycle tracing, round-phase timing, metrics export.

The contract under test: the tracer is a pure OBSERVER — a traced
engine emits token-for-token the same streams with exactly the same
host-sync count as an untraced one, across every scheduling mode
(dense/paged, fused horizons, overlap on/off, speculative drafts,
injected faults) — while the trace itself is well-formed: one closed
request span per request, stack-discipline-clean nesting
(``Tracer.check()``), non-decreasing span stamps even under injected
clock skew, and a valid Chrome/Perfetto export. The metrics side pins
the repo-wide nearest-rank percentile (one definition shared by
``latency_percentiles``, the SLA controller, and the histogram-backed
``EngineMetrics`` columns) and the Prometheus text rendering.
"""

import contextlib
import dataclasses
import glob
import json
import os
import re
import socket
import types
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduce_config
from repro.eval import report as report_mod
from repro.models import Ctx, build_model
from repro.obs import (ADMIT_SPANS, ADMIT_TID, PHASES, SCHED_TID, Histogram,
                       MetricsServer, TraceConfig, Tracer, percentile,
                       render_prometheus)
from repro.serving import (EngineMetrics, FaultPlan, SamplingParams,
                           ServeEngine, SLATarget, deploy,
                           latency_percentiles)
from repro.serving.metrics import SLAController

CTX = Ctx(compute_dtype=jnp.float32)

P1 = np.array([[5, 6, 7, 8, 9]], np.int32)
P2 = np.array([[3, 4, 5, 6, 2]], np.int32)
P3 = np.array([[9, 8, 7, 6, 5]], np.int32)

GREEDY8 = SamplingParams(max_new_tokens=8, eos_id=-1)
SAMPLED6 = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=6,
                          seed=7, eos_id=-1)


@pytest.fixture(scope="module")
def lm():
    rc = reduce_config(REGISTRY["gemma3-1b"])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    return rc, model, params


def _engine(lm, **kw):
    _, model, params = lm
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    if kw.pop("paged", False):
        kw.update(paged=True, page_size=4)
        kw.setdefault("num_pages", 8)
    return ServeEngine(model, params, ctx=CTX, **kw)


def _serve(eng, prompts, sps):
    ids = [eng.submit({"tokens": p}, sp) for p, sp in zip(prompts, sps)]
    outs = {o.request_id: o for o in eng.run_until_drained()}
    return [outs[i] for i in ids]


# ---------------------------------------------------------------------------
# percentile: the one repo-wide nearest-rank definition
# ---------------------------------------------------------------------------

def test_percentile_hand_computed_pins():
    vals = list(range(1, 11))                      # 1..10
    assert percentile(vals, 0) == 1
    assert percentile(vals, 50) == 5               # rank round(.5*9)=4
    assert percentile(vals, 95) == 10              # rank round(.95*9)=9
    assert percentile(vals, 100) == 10
    assert percentile([42.0], 95) == 42.0
    assert percentile(reversed(vals), 50) == 5     # order-insensitive
    assert percentile([], 95) == 0.0               # empty -> 0, not a raise


def test_percentile_rejects_out_of_range_q():
    with pytest.raises(ValueError):
        percentile([1.0], -1)
    with pytest.raises(ValueError):
        percentile([1.0], 100.5)


def test_latency_percentiles_uses_nearest_rank():
    outs = [types.SimpleNamespace(ttft_ms=float(i), tpot_ms=float(10 * i))
            for i in range(1, 11)]
    lat = latency_percentiles(outs)
    assert lat == {"ttft_p50_ms": 5.0, "ttft_p95_ms": 10.0,
                   "tpot_p50_ms": 50.0, "tpot_p95_ms": 100.0}


def test_sla_controller_p95_matches_shared_percentile():
    """The controller's admission decisions ride on the same definition
    the latency columns report — the consolidation invariant."""
    ctl = SLAController(SLATarget(p95_ttft_ms=100.0, window=10),
                        horizon=4, slots=4)
    ctl._window = [(float(i), float(2 * i)) for i in range(1, 11)]
    assert ctl._p95(0) == percentile(range(1, 11), 95) == 10.0
    assert ctl._p95(1) == percentile(range(2, 21, 2), 95) == 20.0


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

def test_histogram_record_mean_percentile():
    h = Histogram(lo=1.0, growth=2.0, n_buckets=8)
    for v in (0.5, 1.5, 3.0, 3.0, 100.0):
        h.record(v)
    assert h.count == 5
    assert h.total == pytest.approx(108.0)
    assert h.mean == pytest.approx(108.0 / 5)
    # percentile reports the covering bucket's geometric middle
    assert h.percentile(50.0) == pytest.approx(8 ** 0.5)   # 3.0 in (2, 4]
    assert h.percentile(0.0) == pytest.approx(0.5 ** 0.5)  # 0.5 in (0, 1]
    assert Histogram().percentile(95.0) == 0.0     # empty histogram


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_histogram_percentile_within_ten_percent_of_the_samples(seed):
    """The default quarter-octave buckets read within 2^(1/8) (under
    10%) of the raw nearest-rank percentile, from 1 ms to a minute."""
    rng = np.random.default_rng(seed)
    vals = np.exp(rng.uniform(np.log(1.0), np.log(60_000.0), 500))
    h = Histogram()
    for v in vals:
        h.record(v)
    assert h.bounds[-1] == pytest.approx(1e-3 * 2 ** 29)   # the old range
    for q in (0.0, 10.0, 50.0, 90.0, 95.0, 99.0, 100.0):
        exact = percentile(vals, q)
        assert abs(h.percentile(q) / exact - 1) < 0.1


def test_histogram_overflow_clamps_to_top_edge():
    h = Histogram(lo=1.0, growth=2.0, n_buckets=4)
    h.record(1e9)                                   # beyond every bound
    assert h.count == 1
    assert h.overflow == 1
    assert h.percentile(95.0) == h.bounds[-1] == 8.0


def test_histogram_merge_and_reset():
    a, b = Histogram(), Histogram()
    a.record(1.0), a.record(2.0)
    b.record(4.0)
    assert a.merge(b) is a
    assert (a.count, a.total) == (3, 7.0)
    with pytest.raises(ValueError, match="config"):
        a.merge(Histogram(lo=0.5))
    a.reset()
    assert (a.count, a.total) == (0, 0.0)
    assert a.percentile(95.0) == 0.0


# ---------------------------------------------------------------------------
# Prometheus rendering
# ---------------------------------------------------------------------------

class _Snap:
    GAUGES = ("kv_bytes",)

    def as_dict(self):
        return {"requests": 3, "kv_bytes": 4096, "occupancy": 0.5}


def test_render_prometheus_types_and_buckets():
    h = Histogram(lo=1.0, growth=2.0, n_buckets=3)
    for v in (0.5, 1.5, 99.0):
        h.record(v)
    text = render_prometheus(_Snap(), {"ttft_ms": h}, prefix="x")
    lines = text.splitlines()
    assert "# TYPE x_requests counter" in lines      # int -> counter
    assert "# TYPE x_kv_bytes gauge" in lines        # declared gauge
    assert "# TYPE x_occupancy gauge" in lines       # float -> gauge
    assert "# TYPE x_ttft_ms histogram" in lines
    # cumulative buckets, terminated by +Inf == count
    assert 'x_ttft_ms_bucket{le="1"} 1' in lines
    assert 'x_ttft_ms_bucket{le="2"} 2' in lines
    assert 'x_ttft_ms_bucket{le="+Inf"} 3' in lines
    assert "x_ttft_ms_count 3" in lines
    assert any(ln.startswith("x_ttft_ms_sum ") for ln in lines)


# ---------------------------------------------------------------------------
# Tracer primitives
# ---------------------------------------------------------------------------

def test_tracer_balanced_spans_pass_check(tmp_path):
    tr = Tracer(TraceConfig())
    tr.name_track(1, "req 0")
    tr.begin(SCHED_TID, "round", 1.0)
    tr.complete(SCHED_TID, "dispatch", 1.0, 0.5, K=4)
    tr.begin(1, "request", 1.1)
    tr.instant(1, "decode-round", 1.2, planned=4)
    tr.end(1, "request", 1.9)
    tr.end(SCHED_TID, "round", 2.0)
    assert tr.check() == []
    chrome = tr.to_chrome()
    assert chrome["displayTimeUnit"] == "ms"
    phs = [e["ph"] for e in chrome["traceEvents"]]
    assert {"B", "E", "X", "i", "M"} <= set(phs)
    p = tmp_path / "trace.json"
    tr.dump_json(str(p))
    assert json.loads(p.read_text())["traceEvents"]


def test_tracer_check_flags_discipline_violations():
    tr = Tracer(TraceConfig())
    tr.begin(0, "round", 1.0)
    assert any("never closed" in p for p in tr.check())
    tr.end(0, "other-name", 2.0)                   # closes the wrong name
    assert any("closes" in p for p in tr.check())
    tr2 = Tracer(TraceConfig())
    tr2.end(0, "round", 1.0)                       # end with no begin
    assert any("without open span" in p for p in tr2.check())


def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(TraceConfig(capacity=16))
    for i in range(20):
        tr.instant(0, f"e{i}", float(i))
    assert len(tr) == 16
    assert tr.dropped == 4
    names = [e.name for e in tr.events]
    assert names[0] == "e4" and names[-1] == "e19"


def test_tracer_clamps_span_stamps_against_backward_clock():
    """Negative skew must not produce end < begin (Perfetto rejects
    it); instants keep their raw stamp so the jump stays visible."""
    tr = Tracer(TraceConfig())
    tr.instant(0, "fault:skew", 3.0, ms=-7000)     # instants keep raw ts
    tr.begin(0, "round", 10.0)
    tr.end(0, "round", 5.0)                        # clock went backward
    assert tr.check() == []
    by_ph = {e.ph: e for e in tr.events}
    assert by_ph["E"].ts_us == by_ph["B"].ts_us == pytest.approx(10.0 * 1e6)
    assert by_ph["i"].ts_us == pytest.approx(3.0 * 1e6)


# ---------------------------------------------------------------------------
# flow links: s/f pairs tying a preempted request's two residencies
# ---------------------------------------------------------------------------


def test_tracer_flow_pair_passes_check_and_exports():
    tr = Tracer(TraceConfig())
    tr.begin(1, "queued", 1.0)
    fid = tr.flow_start(1, "resume", 1.0, count=1)
    tr.end(1, "queued", 2.0)
    tr.begin(1, "request", 2.0)
    tr.flow_end(1, "resume", 2.0, fid)
    tr.end(1, "request", 3.0)
    assert tr.check() == []
    chrome = [e for e in tr.to_chrome()["traceEvents"]
              if e["ph"] in ("s", "f")]
    assert [e["ph"] for e in chrome] == ["s", "f"]
    assert chrome[0]["id"] == chrome[1]["id"] == fid
    assert chrome[1]["bp"] == "e"          # bind to the enclosing slice
    assert "bp" not in chrome[0]


def test_tracer_flow_violations_flagged():
    tr = Tracer(TraceConfig())
    tr.flow_end(0, "resume", 1.0, 99)                 # f with no s
    assert any("without matching s" in p for p in tr.check())

    tr2 = Tracer(TraceConfig())
    tr2.flow_start(0, "resume", 1.0)                  # s never consumed
    assert any("never finished" in p for p in tr2.check())

    tr3 = Tracer(TraceConfig())
    fid = tr3.flow_start(0, "resume", 5.0)
    tr3.flow_end(0, "resume", 4.0, fid)               # ends before it starts
    assert any("before it starts" in p for p in tr3.check())

    tr4 = Tracer(TraceConfig())
    fid = tr4.flow_start(0, "resume", 1.0)
    tr4.flow_end(0, "other", 2.0, fid)                # name mismatch
    assert any("closes s" in p for p in tr4.check())


def test_preemption_links_residencies_with_flow(lm):
    """A preempted-and-resumed request's two slot residencies are tied
    by a ``resume`` flow pair: the Perfetto arrow from the eviction's
    re-queue to the replayed admission. One pair per round trip, all
    consumed, and the trace still passes check()."""
    eng = _engine(lm, paged=True, num_pages=5, preempt_limit=16,
                  trace=TraceConfig())
    _serve(eng, (P1, P2), (GREEDY8, GREEDY8))
    m = eng.metrics()
    assert m.preemptions >= 1 and m.resumed_requests >= 1
    starts = [e for e in eng.trace.events if e.ph == "s"]
    ends = [e for e in eng.trace.events if e.ph == "f"]
    assert len(starts) == m.preemptions == len(ends)
    assert {e.name for e in starts + ends} == {"resume"}
    assert sorted(e.flow_id for e in starts) \
        == sorted(e.flow_id for e in ends)
    assert eng.trace.check() == []


def test_flow_closed_when_preempted_request_dies_queued(lm):
    """An abort that lands while the victim sits re-queued must still
    consume its flow start (flow_end at retirement) — otherwise the
    trace leaks a dangling ``s`` and check() flags it."""
    eng = _engine(lm, paged=True, num_pages=5, preempt_limit=16,
                  trace=TraceConfig())
    r1 = eng.submit({"tokens": P1}, GREEDY8)
    r2 = eng.submit({"tokens": P2}, GREEDY8)
    for _ in range(64):
        if eng.metrics().preemptions:
            break
        eng.step()
    assert eng.metrics().preemptions >= 1
    assert eng.num_pending == 1            # the evicted younger request
    out = eng.abort(r2)
    assert out.finish_reason == "abort"
    outs = eng.run_until_drained()
    assert [o.request_id for o in outs] == [r1]
    assert eng.trace.check() == []         # no dangling flow starts


# ---------------------------------------------------------------------------
# traced == untraced: streams, syncs, and scheduling are untouched
# ---------------------------------------------------------------------------

def _run_mode(lm, trace, **kw):
    eng = _engine(lm, trace=TraceConfig() if trace else None, **kw)
    outs = _serve(eng, (P1, P2, P3), (GREEDY8, SAMPLED6, GREEDY8))
    return outs, eng


@pytest.mark.parametrize("kw", [
    dict(horizon=1),                               # dense, per-token
    dict(horizon=16),                              # dense, fused
    dict(horizon=16, paged=True),                  # paged, fused
    dict(horizon=4, overlap=False),                # serial rounds
], ids=["dense-h1", "dense-h16", "paged-h16", "no-overlap"])
def test_traced_equals_untraced(lm, kw):
    base, ref_eng = _run_mode(lm, False, **kw)
    outs, eng = _run_mode(lm, True, **kw)
    for b, g in zip(base, outs):
        assert g.token_ids == b.token_ids
        assert g.finish_reason == b.finish_reason
    assert eng.decode_syncs == ref_eng.decode_syncs
    assert eng.metrics().overlap_rounds == ref_eng.metrics().overlap_rounds
    assert eng.trace.check() == []
    spans = eng.trace.request_spans()
    assert len(spans) == 3 and all(s["closed"] for s in spans.values())


def test_traced_equals_untraced_draft_arm():
    def run(trace):
        pipe = deploy("gemma3-1b", "int8", slots=2, max_len=32, smoke=True,
                      paged=True, page_size=4, horizon=4,
                      draft_spec="wfp4a8",
                      trace=TraceConfig() if trace else None)
        outs = _serve(pipe.engine, (P1, P2), (GREEDY8, GREEDY8))
        return outs, pipe.engine

    base, ref_eng = run(False)
    outs, eng = run(True)
    assert [o.token_ids for o in outs] == [o.token_ids for o in base]
    assert eng.decode_syncs == ref_eng.decode_syncs
    assert eng.trace.check() == []
    # every verify round left its instant, stamped with the draft ledger
    verifies = [e for e in eng.trace.events if e.name == "verify"]
    assert verifies and all(
        e.args["drafted"] >= e.args["accepted"] >= 0 for e in verifies)


def test_traced_faulted_run_keeps_monotonic_spans(lm):
    """Injected clock skew jumps the engine clock mid-run: the trace
    records the fault instants on the scheduler track and every span's
    B/E stamps stay non-decreasing (floor-clamped), so the export is
    still loadable."""
    def run(trace):
        plan = FaultPlan(skew_at=[(2, 600_000.0)])
        eng = _engine(lm, slots=1,
                      trace=TraceConfig() if trace else None, faults=plan)
        dl = SamplingParams(max_new_tokens=8, eos_id=-1,
                            deadline_ms=60_000.0)
        return _serve(eng, (P1, P2), (dl, GREEDY8)), eng

    base, _ = run(False)
    outs, eng = run(True)
    assert [(o.token_ids, o.finish_reason) for o in outs] \
        == [(o.token_ids, o.finish_reason) for o in base]
    assert outs[0].finish_reason == "deadline"
    tr = eng.trace
    assert tr.check() == []
    assert any(e.name == "fault:skew" for e in tr.events)
    # the expired request's span closed with the deadline marker inside
    spans = tr.request_spans()
    assert spans[0]["closed"] and spans[0]["reason"] == "deadline"
    assert "deadline" in spans[0]["events"]
    # per-track B/E stamps never run backward, skew notwithstanding
    last = {}
    for e in tr.events:
        if e.ph in ("B", "E"):
            assert e.ts_us >= last.get(e.tid, 0.0)
            last[e.tid] = e.ts_us


def test_lifecycle_event_order_and_phase_totals(lm):
    eng = _engine(lm, paged=True, horizon=4, trace=TraceConfig())
    _serve(eng, (P1, P2), (GREEDY8, SAMPLED6))
    spans = eng.trace.request_spans()
    for rid, span in spans.items():
        names = span["events"]                     # child names, in order
        assert names[0] == "queued"
        assert names[1] == "prefill"
        assert names[-1] == "retired"
        assert "decode-round" in names
        assert span["end_us"] >= span["begin_us"]
    # the scheduler track carries round spans with phase X events inside
    sched = [e for e in eng.trace.events if e.tid == SCHED_TID]
    assert any(e.ph == "B" and e.name == "round" for e in sched)
    assert {e.name for e in sched if e.ph == "X"} <= set(PHASES)
    m = eng.metrics()
    for p in PHASES:
        assert getattr(m, f"phase_{p}_ms") >= 0.0
    assert m.phase_admit_ms > 0 and m.phase_dispatch_ms > 0
    # histogram-backed latency columns populate on retirement
    assert m.ttft_p95_ms > 0 and m.tpot_p95_ms > 0
    assert m.ttft_p50_ms <= m.ttft_p95_ms


def test_untraced_engine_reports_zero_phase_time(lm):
    """The zero-cost path: an untraced engine accumulates no phase
    time at all (the timers never run), while the always-on latency
    histograms still feed the ttft/tpot columns."""
    eng = _engine(lm, horizon=4)
    _serve(eng, (P1,), (GREEDY8,))
    assert eng.trace is None
    m = eng.metrics()
    assert all(getattr(m, f"phase_{p}_ms") == 0.0 for p in PHASES)
    assert m.ttft_p95_ms > 0 and m.tpot_p95_ms > 0


def test_engine_prometheus_export(lm):
    eng = _engine(lm, horizon=4, trace=TraceConfig())
    _serve(eng, (P1,), (GREEDY8,))
    text = eng.prometheus()
    assert "# TYPE repro_serving_decode_syncs counter" in text
    assert "# TYPE repro_serving_ttft_ms histogram" in text
    assert 'repro_serving_ttft_ms_bucket{le="+Inf"} 1' in text
    for p in PHASES:
        assert f"repro_serving_round_phase_{p}_ms_count" in text


def test_trace_config_validates_capacity():
    with pytest.raises(ValueError, match="capacity"):
        TraceConfig(capacity=4)


# ---------------------------------------------------------------------------
# live /metrics endpoint (obs.promhttp)
# ---------------------------------------------------------------------------


def test_metrics_server_serves_renderer_at_metrics_path():
    with MetricsServer(lambda: "up 1\n") as srv:
        assert srv.url == f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            assert resp.read() == b"up 1\n"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/other", timeout=5)
        assert ei.value.code == 404


def test_metrics_server_scrapes_live_engine(lm):
    """The renderer runs per scrape: counters served before work differ
    from counters served after — a live endpoint, not a snapshot."""
    eng = _engine(lm)
    with MetricsServer(eng.prometheus) as srv:
        def scrape():
            with urllib.request.urlopen(srv.url, timeout=5) as r:
                return r.read().decode()
        before = scrape()
        _serve(eng, (P1,), (GREEDY8,))
        after = scrape()

    def synced(text):
        line = [ln for ln in text.splitlines()
                if ln.startswith("repro_serving_synced_tokens ")]
        return float(line[0].split()[-1])

    assert synced(before) == 0
    assert synced(after) > 0


def test_metrics_server_render_failure_is_500_and_survives():
    calls = []

    def render():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("collector down")
        return "ok 1\n"

    with MetricsServer(render) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url, timeout=5)
        assert ei.value.code == 500
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert resp.read() == b"ok 1\n"    # server outlived the error


def test_metrics_server_graceful_shutdown_frees_port():
    srv = MetricsServer(lambda: "x 0\n").start()
    port = srv.port
    urllib.request.urlopen(srv.url, timeout=5).read()
    srv.close()
    srv.close()                                # idempotent
    # the listener is gone: connections are refused, and the port
    # rebinds immediately (socket closed, not leaked to TIME_WAIT)
    with pytest.raises(OSError):
        urllib.request.urlopen(srv.url, timeout=1)
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        s.bind(("127.0.0.1", port))
    finally:
        s.close()


def test_metrics_snapshot_carries_histogram_fields():
    names = {f.name for f in dataclasses.fields(EngineMetrics)}
    assert {"ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
            "phase_admit_ms", "phase_dispatch_ms", "phase_sync_ms",
            "phase_walk_ms"} <= names


# ---------------------------------------------------------------------------
# report schema v5: round_phases rides the sweep rows
# ---------------------------------------------------------------------------

def test_report_v4_upgrades_to_v5_and_round_trips():
    v4 = {"schema": 4, "kind": "repro.eval", "arch": "x", "git_rev": None,
          "config": {}, "rows": [
              {"fmt": "int8", "spec": "w8", "ttft_p95_ms": 9.0,
               "tpot_p95_ms": 2.0, "pair_scores": []}]}
    loaded = report_mod.load(json.dumps(v4))
    assert loaded["schema"] == report_mod.SCHEMA_VERSION == 5
    assert loaded["rows"][0]["round_phases"] is None   # untraced sentinel
    assert loaded["rows"][0]["ttft_p95_ms"] == 9.0     # payload preserved
    assert report_mod.load(report_mod.dump(loaded)) == loaded


def test_report_with_round_phases_round_trips():
    r = report_mod.make_report(arch="x", rows=[{
        "fmt": "int8", "spec": "w8", "mean_bleu": 1.0,
        "round_phases": {"admit_ms": 1.5, "dispatch_ms": 2.5,
                         "sync_ms": 0.1, "walk_ms": 0.4},
        "pair_scores": []}])
    assert report_mod.load(report_mod.dump(r)) == r


def test_quant_sweep_traced_records_round_phases():
    from repro.eval import quant_sweep
    rc = reduce_config(REGISTRY["nllb600m"])
    params = build_model(rc).init(jax.random.PRNGKey(0))
    rows = quant_sweep(
        rc, ["int8"], params=params, pair_list=[("hin", "eng")],
        languages=["hin", "eng"], n_sent=2,
        deploy_kwargs={"slots": 2, "max_len": 16, "ctx": CTX},
        trace=True, log=lambda *_: None)
    rp = rows[0].round_phases
    assert rp is not None
    assert set(rp) == {f"{p}_ms" for p in PHASES}
    assert rp["admit_ms"] > 0 and rp["dispatch_ms"] > 0
    # the traced column survives the report round-trip
    rep = report_mod.make_report(arch=rc.name,
                                 rows=[r.as_row() for r in rows])
    assert report_mod.load(report_mod.dump(rep)) == rep
    assert rep["rows"][0]["round_phases"] == rp


# ---------------------------------------------------------------------------
# the profiler's clock: layer scopes in the programs, spans as annotations
# ---------------------------------------------------------------------------

DECODE_SCOPES = {"embed", "dec.layers", "dec.attn", "dec.cross", "dec.ffn",
                 "head", "sampler"}
PREFILL_SCOPES = DECODE_SCOPES | {"enc.attn", "enc.ffn", "dec.cross.kv"}


def _scopes(text: str) -> set:
    """The innermost layer scope of each op_name in a compiled text."""
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        inner = [c for c in name.split("/") if c in PREFILL_SCOPES]
        if inner:
            found.add(inner[-1])
    return found


def _compiled_programs(paged: bool):
    """The compiled text of a smoke nllb600m engine's decode scan and
    prefill, lowered with the arguments its dispatch and admission
    pass."""
    pipe = deploy("nllb600m", "int4", slots=2, max_len=32, smoke=True,
                  paged=paged, page_size=4, horizon=4)
    eng = pipe.engine
    alive, rem, eos = eng._scan_masks()
    decode = eng._make_horizon_fn(2).lower(
        eng.params, eng.cur, eng.cache, eng._temps, eng._top_ks,
        eng._top_ps, eng._keys, eng._offsets, alive, rem, eos,
        eng._poison_arr(2)).compile().as_text()
    src = {"src_tokens": np.zeros((1, 6), np.int32)}
    if paged:
        inputs = eng._group_inputs([jnp.zeros((1, 1), jnp.int32)], [src])
        prefill = eng._prefill_paged_fn.lower(
            eng.params, inputs, jnp.ones((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, eng.max_pages), jnp.int32), eng.cache,
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32),
            jnp.stack([jax.random.PRNGKey(0)]))
    else:
        inputs = dict(src, tgt_in=jnp.zeros((1, 1), jnp.int32),
                      lengths=jnp.ones((1,), jnp.int32))
        prefill = eng._prefill_fn.lower(
            eng.params, inputs, jnp.int32(1), jnp.float32(0.0),
            jnp.int32(0), jnp.float32(1.0), jax.random.PRNGKey(0))
    return decode, prefill.compile().as_text()


def _computations(text: str):
    """A compiled module's computations with their metadata dropped."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    return [re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in lines[start:]]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_compiled_programs_carry_the_layer_scopes(paged, monkeypatch):
    """The decode scan and the prefill name every layer in their
    operations' op_name metadata, and the scopes are metadata only: with
    ``jax.named_scope`` made a no-op the compiled computations are the
    same, instruction for instruction."""
    decode, prefill = _compiled_programs(paged)
    assert _scopes(decode) == DECODE_SCOPES
    assert _scopes(prefill) == PREFILL_SCOPES
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_decode, bare_prefill = _compiled_programs(paged)
    assert not _scopes(bare_decode)
    assert _computations(bare_decode) == _computations(decode)
    assert _computations(bare_prefill) == _computations(prefill)


def _host_events(path):
    """(thread line, name, start_ns, end_ns) of every host-plane event
    of a profile written under ``path``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote nothing"
    pd = ProfileData.from_file(files[0])
    return [(line.name, ev.name.split("#", 1)[0], ev.start_ns, ev.end_ns)
            for plane in pd.planes if not plane.name.startswith("/device")
            for line in plane.lines for ev in line.events]


def test_rounds_sit_on_the_profilers_host_plane(lm, tmp_path):
    """Under a running profiler a traced engine's phases and admission
    sub-spans appear on the host plane by name, each sub-span inside an
    ``admit`` on the same thread; the Tracer keeps its scheduler track
    to the round and its four phases, and the sub-spans on their own."""
    eng = _engine(lm, paged=True, horizon=4, trace=TraceConfig(),
                  faults=FaultPlan())
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, (P1, P2, P3), (GREEDY8, SAMPLED6, GREEDY8))
    events = _host_events(str(tmp_path))
    names = {n for _, n, _, _ in events}
    assert set(PHASES) | set(ADMIT_SPANS) <= names
    admits = [(t, a, b) for t, n, a, b in events if n == "admit"]
    subs = [(t, n, a, b) for t, n, a, b in events if n in ADMIT_SPANS]
    for t, n, a, b in subs:
        assert any(t == t2 and a2 <= a and b <= b2
                   for t2, a2, b2 in admits), n
    tr = eng.trace
    assert tr.check() == []
    sched = [e for e in tr.events if e.tid == SCHED_TID]
    assert {e.name for e in sched if e.ph == "X"} == set(PHASES)
    assert {e.name for e in sched if e.ph != "X"} == {"round"}
    admit_track = [e for e in tr.events if e.tid == ADMIT_TID]
    assert {e.name for e in admit_track} == set(ADMIT_SPANS)
    assert all(e.ph == "X" for e in admit_track)
    # each sub-span lies inside an admit phase on the engine clock too
    phases = [(e.ts_us, e.ts_us + e.dur_us) for e in sched
              if e.ph == "X" and e.name == "admit"]
    for e in admit_track:
        assert any(a - 1.0 <= e.ts_us and e.ts_us + e.dur_us <= b + 1.0
                   for a, b in phases), e.name
    tracks = {e["tid"]: e["args"]["name"] for e in tr.to_chrome()["traceEvents"]
              if e["name"] == "thread_name"}
    assert tracks[ADMIT_TID] == "admission"
