"""Streaming request API, overlapped scheduler, and SLA-aware admission.

The overlapped scheduler's contract: with ``overlap=True`` (the
default) the engine dispatches horizon N+1 from the in-flight scan's
device carry while the host walks horizon N's token block — and the
emitted streams are token-for-token identical to serial
dispatch-then-walk rounds (``overlap=False``) at any horizon, dense
and paged, through mid-stream admission and abort. Streaming delivery
(``submit(on_token=...)``, ``stream_request``, ``stream(on_round=)``)
must observe exactly the tokens the drained RequestOutput reports.

Also covered: the frozen EngineMetrics snapshot (reset_metrics zeroes
every non-gauge field — asserted by dataclass introspection, so a new
counter can't dodge the reset), the SLAController retune policy, and
the report schema v3 -> v4 upgrade (per-format ttft/tpot columns).
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, reduce_config
from repro.eval import report as report_mod
from repro.models import Ctx, build_model
from repro.serving import (EngineMetrics, RoundBudgetExhausted,
                           SamplingParams, ServeEngine, SLATarget,
                           TraceConfig, deploy, greedy_generate, translate)
from repro.serving.metrics import SLAController

CTX = Ctx(compute_dtype=jnp.float32)


def _lm(name="gemma3-1b"):
    rc = reduce_config(REGISTRY[name])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    return rc, model, params


def _prompts(rc, n=2):
    return [jax.random.randint(jax.random.PRNGKey(i + 1), (1, 4 + 2 * i),
                               0, rc.vocab_size) for i in range(n)]


# ---------------------------------------------------------------------------
# overlapped == serial equivalence
# ---------------------------------------------------------------------------

def _drain_by_id(eng, ids):
    outs = {o.request_id: o for o in eng.run_until_drained()}
    return [outs[i] for i in ids]


def test_overlap_equivalence_dense_mixed_params():
    """Overlapped dispatch must not change a single token: greedy and
    seeded top-p slots, plus a request admitted mid-stream."""
    rc, model, params = _lm()
    p1, p2 = _prompts(rc)
    sp_g = SamplingParams(max_new_tokens=9)
    sp_s = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=7,
                          seed=3)

    def run(overlap, K):
        eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                          horizon=K, overlap=overlap)
        ids = [eng.submit({"tokens": p1}, sp_g)]
        early = eng.step()               # first horizon in flight
        ids.append(eng.submit({"tokens": p2}, sp_s))
        outs = {o.request_id: o for o in early + eng.run_until_drained()}
        return [outs[i] for i in ids], eng

    base, serial = run(False, 4)
    assert serial.overlap_rounds == 0    # serial engine never runs ahead
    for K in (4, 8):
        got, eng = run(True, K)
        for b, g in zip(base if K == 4 else run(False, K)[0], got):
            assert g.token_ids == b.token_ids, K
            assert g.finish_reason == b.finish_reason
        if K == 4:
            # 8 decode tokens across 4-step blocks: some round must
            # have dispatched ahead (at K=8 the budget fits one block,
            # so there is legitimately nothing to run ahead of)
            assert eng.overlap_rounds > 0, \
                "no round overlapped host walk with dispatch"


def test_overlap_equivalence_paged():
    """Paged engine: overlapped and serial rounds emit the same streams
    and both reclaim every page."""
    def run(overlap):
        pipe = deploy("gemma3-1b", "int8", slots=2, max_len=32, smoke=True,
                      paged=True, page_size=4, horizon=4, overlap=overlap)
        eng = pipe.engine
        p1, p2 = _prompts(pipe.cfg)
        ids = [eng.submit({"tokens": p1}, SamplingParams(max_new_tokens=8)),
               eng.submit({"tokens": p2},
                          SamplingParams(temperature=0.7, top_k=8,
                                         max_new_tokens=6, seed=11))]
        outs = _drain_by_id(eng, ids)
        assert eng.allocator.pages_in_use == 0
        return outs, eng

    base, _ = run(False)
    got, eng = run(True)
    for b, g in zip(base, got):
        assert g.token_ids == b.token_ids
        assert g.finish_reason == b.finish_reason
    assert eng.overlap_rounds > 0


def test_overlap_sync_counts_match_serial():
    """Dispatch-ahead must not skew the sync ledger: a dead ahead-block
    is dropped without a host sync, so overlapped and serial engines
    report identical decode_syncs for the same work."""
    rc, model, params = _lm()
    p = _prompts(rc, 1)[0]
    sp = SamplingParams(max_new_tokens=9)    # 1 prefill + 8 decode

    def syncs(overlap):
        eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                          horizon=4, overlap=overlap)
        eng.submit({"tokens": p}, sp)
        eng.run_until_drained()
        return eng.decode_syncs

    assert syncs(True) == syncs(False) == 2


def test_draft_arm_disables_overlap():
    """Speculative rounds are host decision points: a draft-armed
    engine streams through the same API but never dispatches ahead,
    and its tokens still match the target-only engine."""
    kw = dict(slots=1, max_len=32, smoke=True)
    target = deploy("gemma3-1b", "int8", **kw)
    spec = deploy("gemma3-1b", "int8", draft_spec="wfp4a8",
                  draft_lookahead=4, **kw)
    p = _prompts(target.cfg, 1)[0]
    sp = SamplingParams(max_new_tokens=8)
    ref = target.generate([p[0]], sp)[0]
    out = spec.generate([p[0]], sp)[0]
    assert out.token_ids == ref.token_ids
    assert spec.engine.metrics().overlap_rounds == 0
    assert spec.engine.metrics().verify_calls > 0


# ---------------------------------------------------------------------------
# streaming delivery
# ---------------------------------------------------------------------------

def test_on_token_callback_sees_every_token():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=24, ctx=CTX,
                      horizon=4)
    p = _prompts(rc, 1)[0]
    live = []
    rid = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=7),
                     on_token=live.append)
    out = _drain_by_id(eng, [rid])[0]
    assert live == out.token_ids
    assert out.ttft_ms > 0.0
    assert out.tpot_ms > 0.0
    # TTFT is part of the total span, never larger than it
    assert out.stats.ttft_s <= out.stats.total_s


def test_stream_request_tokens_match_drained_output():
    """stream_request yields exactly the finished output's token list,
    returns the RequestOutput via StopIteration.value, and other
    in-flight requests stay claimable afterwards."""
    rc, model, params = _lm()
    p1, p2 = _prompts(rc)
    sp = SamplingParams(max_new_tokens=6)

    ref_eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                          horizon=4)
    ids = [ref_eng.submit({"tokens": p1}, sp),
           ref_eng.submit({"tokens": p2}, sp)]
    refs = _drain_by_id(ref_eng, ids)

    eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                      horizon=4)
    other = eng.submit({"tokens": p2}, sp)
    gen = eng.stream_request({"tokens": p1}, sp)
    toks = []
    while True:
        try:
            toks.append(next(gen))
        except StopIteration as fin:
            out = fin.value
            break
    assert toks == out.token_ids == refs[0].token_ids
    assert out.finish_reason == refs[0].finish_reason
    rest = eng.run_until_drained()
    assert [o.request_id for o in rest] == [other]
    assert rest[0].token_ids == refs[1].token_ids


class _DeviceFault(RuntimeError):
    """Stands in for jax.errors.JaxRuntimeError (a RuntimeError)."""


def test_stream_request_propagates_device_errors():
    """A fault raised inside a round (a device OOM surfaces as a
    RuntimeError subclass) reaches the streaming caller; it does not
    end the stream as if the request had drained."""
    assert issubclass(jax.errors.JaxRuntimeError, RuntimeError)
    rc, model, params = _lm()
    p1, _ = _prompts(rc)
    eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                      horizon=4)

    def fault(*args, **kwargs):
        raise _DeviceFault("device lost")

    eng._dispatch_horizon = fault
    gen = eng.stream_request({"tokens": p1}, SamplingParams(max_new_tokens=6))
    with pytest.raises(_DeviceFault):
        list(gen)


def test_stream_request_ends_quietly_on_round_budget():
    """Round-budget exhaustion is typed, and stream_request still ends
    the stream on it: the tokens so far, then a None output."""
    rc, model, params = _lm()
    p1, _ = _prompts(rc)
    sp = SamplingParams(max_new_tokens=12)
    eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                      horizon=4)
    eng.submit({"tokens": p1}, sp)
    with pytest.raises(RoundBudgetExhausted):
        eng.run_until_drained(max_steps=1)

    ref = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                      horizon=4)
    full = _drain_by_id(ref, [ref.submit({"tokens": p1}, sp)])[0].token_ids
    eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                      horizon=4)
    rounds = eng._rounds
    eng._rounds = lambda horizon=None: rounds(horizon, max_rounds=1)
    gen = eng.stream_request({"tokens": p1}, sp)
    toks = []
    while True:
        try:
            toks.append(next(gen))
        except StopIteration as fin:
            out = fin.value
            break
    assert out is None
    assert 0 < len(toks) < len(full) and toks == full[:len(toks)]


def test_stream_yields_per_finish_and_on_round_admission():
    """stream() yields each output as its request retires; arrivals
    submitted from the on_round callback keep the loop alive (the
    bench_serving Poisson driver's contract)."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                      horizon=4)
    p1, p2 = _prompts(rc)
    sp = SamplingParams(max_new_tokens=5)
    ids = [eng.submit({"tokens": p1}, sp)]

    def on_round():
        if len(ids) == 1:
            ids.append(eng.submit({"tokens": p2}, sp))

    outs = list(eng.stream(on_round=on_round))
    assert sorted(o.request_id for o in outs) == sorted(ids)
    assert len(ids) == 2                 # the callback really admitted
    # a drained engine exits before the first round: no yields, no calls
    calls = []
    assert list(eng.stream(on_round=lambda: calls.append(1))) == []
    assert calls == []


def test_abort_from_own_on_token_callback():
    """A request may abort itself from its streaming callback mid-walk:
    tokens truncate at the callback's position, abort() hands the
    output to the callback's caller, and the engine keeps serving."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=32, ctx=CTX,
                      horizon=4)
    p = _prompts(rc, 1)[0]
    seen, got = [], []

    def cb(tok):
        seen.append(tok)
        if len(seen) == 3:
            got.append(eng.abort(rid))

    rid = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=16),
                     on_token=cb)
    assert eng.run_until_drained() == []     # abort() returned the output
    out = got[0]
    assert out.finish_reason == "abort"
    assert out.token_ids == seen and len(seen) == 3
    assert out.stats.new_tokens == 3
    rid2 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=4))
    outs = eng.run_until_drained()
    assert [o.request_id for o in outs] == [rid2]
    assert outs[0].num_generated == 4


# ---------------------------------------------------------------------------
# EngineMetrics
# ---------------------------------------------------------------------------

def test_metrics_snapshot_is_complete_and_frozen():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                      horizon=4)
    eng.submit({"tokens": _prompts(rc, 1)[0]},
               SamplingParams(max_new_tokens=9))
    eng.run_until_drained()
    m = eng.metrics()
    assert isinstance(m, EngineMetrics)
    assert m.decode_syncs == eng.decode_syncs > 0
    assert m.synced_tokens > 0 and m.occupancy > 0
    assert m.overlap_rounds == eng.overlap_rounds > 0
    with pytest.raises(dataclasses.FrozenInstanceError):
        m.decode_syncs = 0
    assert set(m.as_dict()) == {f.name
                                for f in dataclasses.fields(EngineMetrics)}


def test_reset_metrics_zeroes_every_non_gauge_field():
    """Introspective reset check: any counter added to EngineMetrics
    without joining the reset (or declaring itself a gauge) fails here."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                      horizon=4)
    eng.submit({"tokens": _prompts(rc, 1)[0]},
               SamplingParams(max_new_tokens=9))
    eng.run_until_drained()
    eng.reset_metrics()
    m = eng.metrics()
    for f in dataclasses.fields(EngineMetrics):
        if f.name not in EngineMetrics.GAUGES:
            assert getattr(m, f.name) == 0, \
                f"{f.name} survived reset_metrics()"
    # gauges reflect live engine state, not accumulation
    assert m.kv_cache_bytes > 0


def test_reset_metrics_zeroes_traced_histograms():
    """The introspective test above guarantees the EngineMetrics fields
    zero; this pins the backing accumulators actually RECORDING under
    tracing first — a reset test over fields that never moved proves
    nothing."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                      horizon=4, trace=TraceConfig())
    eng.submit({"tokens": _prompts(rc, 1)[0]},
               SamplingParams(max_new_tokens=9))
    eng.run_until_drained()
    m = eng.metrics()
    assert m.ttft_p50_ms > 0 and m.ttft_p95_ms > 0
    assert m.tpot_p50_ms > 0 and m.tpot_p95_ms > 0
    assert m.phase_admit_ms > 0 and m.phase_dispatch_ms > 0
    assert m.phase_walk_ms > 0
    eng.reset_metrics()
    m = eng.metrics()
    for name in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
                 "tpot_p95_ms", "phase_admit_ms", "phase_dispatch_ms",
                 "phase_sync_ms", "phase_walk_ms"):
        assert getattr(m, name) == 0.0, f"{name} survived reset_metrics()"


# ---------------------------------------------------------------------------
# SLA-aware admission
# ---------------------------------------------------------------------------

def _obs(ttft_ms, tpot_ms):
    return types.SimpleNamespace(ttft_ms=ttft_ms, tpot_ms=tpot_ms)


def test_sla_target_validation():
    with pytest.raises(ValueError, match="constrains nothing"):
        SLATarget()
    with pytest.raises(ValueError, match="positive"):
        SLATarget(p95_ttft_ms=-1)
    with pytest.raises(ValueError, match="window"):
        SLATarget(p95_ttft_ms=10, window=0)
    with pytest.raises(ValueError, match="max_horizon"):
        SLATarget(p95_ttft_ms=10, min_horizon=4, max_horizon=2)


def test_sla_controller_ttft_breach_halves_admission_knobs():
    c = SLAController(SLATarget(p95_ttft_ms=10.0, window=4),
                      horizon=8, slots=4)
    assert c.holding() is None           # no full window yet
    for _ in range(3):
        assert not c.observe(_obs(100.0, 1.0))
    assert c.retunes == 0 and c.horizon == 8
    assert c.observe(_obs(100.0, 1.0))   # window full -> retune fires
    assert (c.horizon, c.prefill_cap, c.retunes) == (4, 2, 1)
    assert c.holding() is False


def test_sla_controller_tpot_breach_doubles_horizon():
    c = SLAController(SLATarget(p95_tpot_ms=1.0, window=2, max_horizon=16),
                      horizon=4, slots=2)
    for _ in range(2):
        c.observe(_obs(0.0, 50.0))
    assert c.horizon == 8                # longer scans amortize syncs
    for _ in range(2):
        c.observe(_obs(0.0, 50.0))
    for _ in range(2):
        c.observe(_obs(0.0, 50.0))
    assert c.horizon == 16               # clamped at max_horizon
    assert c.holding() is False


def test_sla_controller_relaxes_toward_deploy_config():
    c = SLAController(SLATarget(p95_ttft_ms=10.0, p95_tpot_ms=100.0,
                                window=1), horizon=8, slots=4)
    c.observe(_obs(50.0, 1.0))           # breach: 8/4 -> 4/2
    assert (c.horizon, c.prefill_cap) == (4, 2)
    c.observe(_obs(1.0, 1.0))            # good window: horizon first
    assert (c.horizon, c.prefill_cap) == (8, 2)
    c.observe(_obs(1.0, 1.0))            # then the prefill cap
    assert (c.horizon, c.prefill_cap) == (8, 4)
    assert c.holding() is True
    retunes = c.retunes
    c.observe(_obs(1.0, 1.0))            # at deploy config: no-op
    assert c.retunes == retunes


def test_deploy_sla_attaches_controller_and_serves():
    pipe = deploy("gemma3-1b", "int8", slots=2, max_len=16, smoke=True,
                  horizon=4,
                  sla=SLATarget(p95_ttft_ms=60_000.0, p95_tpot_ms=60_000.0,
                                window=2))
    eng = pipe.engine
    assert eng.sla is not None and eng.sla.horizon == 4
    outs = pipe.generate([p[0] for p in _prompts(pipe.cfg)],
                         SamplingParams(max_new_tokens=6))
    assert all(o.num_generated == 6 for o in outs)
    # two retirements filled the window: the controller has observed
    assert eng.sla.windows >= 1
    assert eng.sla.holding() is True     # targets are unmissable here


# ---------------------------------------------------------------------------
# legacy wrapper deprecation
# ---------------------------------------------------------------------------

def test_legacy_wrappers_warn_deprecation():
    rc, model, params = _lm()
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0,
                              rc.vocab_size)
    with pytest.warns(DeprecationWarning, match="greedy_generate"):
        greedy_generate(model, CTX, params, {"tokens": toks}, steps=2,
                        max_len=8)
    nc = reduce_config(REGISTRY["nllb600m"])
    nmodel = build_model(nc)
    nparams = nmodel.init(jax.random.PRNGKey(0))
    src = jax.random.randint(jax.random.PRNGKey(1), (1, nc.enc_len), 0,
                             nc.vocab_size)
    with pytest.warns(DeprecationWarning, match="translate") as rec:
        translate(nmodel, CTX, nparams, src, 8, steps=2, max_len=8)
    # translate delegates internally, it must not warn twice
    assert len([w for w in rec.list
                if issubclass(w.category, DeprecationWarning)]) == 1


# ---------------------------------------------------------------------------
# report schema v4 latency roll-up (upgrade chains to current)
# ---------------------------------------------------------------------------

def _v3_report():
    return {"schema": 3, "kind": "repro.eval", "arch": "x", "git_rev": None,
            "config": {}, "rows": [
                {"fmt": "int8", "spec": "w8",
                 "pair_scores": [
                     {"src": "hin", "tgt": "eng", "bleu": 0.5,
                      "ttft_p95_ms": 12.0, "tpot_p95_ms": 3.0},
                     {"src": "eng", "tgt": "hin", "bleu": 0.4,
                      "ttft_p95_ms": 20.0, "tpot_p95_ms": 2.5}]},
                {"fmt": "bf16", "spec": "w16", "pair_scores": []}]}


def test_report_v3_upgrades_to_current():
    loaded = report_mod.load(json.dumps(_v3_report()))
    assert loaded["schema"] == report_mod.SCHEMA_VERSION == 5
    row = loaded["rows"][0]
    # worst direction over the pair grid — what an SLATarget is set on
    assert row["ttft_p95_ms"] == 20.0
    assert row["tpot_p95_ms"] == 3.0
    # no per-pair latency recorded -> explicit None, not a KeyError
    assert loaded["rows"][1]["ttft_p95_ms"] is None
    assert loaded["rows"][1]["tpot_p95_ms"] is None
    # v4 -> v5: pre-trace rows gain the untraced sentinel
    assert all(r["round_phases"] is None for r in loaded["rows"])
    assert report_mod.load(report_mod.dump(loaded)) == loaded
