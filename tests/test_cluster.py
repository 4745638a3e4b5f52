"""Cluster serving: replica routing, merged metrics, scale-out parity.

The subsystem's standing bar: a routed (data-parallel) or sharded
(tensor-parallel) deployment serves token-for-token the streams a lone
single-device engine serves. Fast tests cover the router's control
plane on one device — least-outstanding-work placement, priority-aware
competition counts, saturated-replica failover and the cluster-wide
``EngineSaturated`` re-raise, abort/deadline routed to the owning
replica, global id remapping, and metric merging (counters sum,
percentiles from ``Histogram.merge``, labelled Prometheus rendering).
A seeded hypothesis property checks fairness: under mixed priorities
no replica starves. The ``slow``-marked subprocess tests force 8 host
devices and drive the real parity grids: ``mesh=tp_mesh(K)`` engines
and ``deploy_replicas`` clusters vs the single-device reference, dense
and paged, horizon 1 and 16, greedy and seeded sampling.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (ReplicaRouter, deploy_replicas, parse_mesh_spec,
                           tp_mesh)
from repro.configs import REGISTRY, reduce_config
from repro.eval import assert_serving_equivalence
from repro.models import Ctx, build_model
from repro.serving import (EngineSaturated, SamplingParams, ServeEngine,
                           deploy)
from repro.serving.metrics import EngineMetrics, merge_metrics
from repro.obs import Histogram
from repro.obs.metrics import render_prometheus_labeled

CTX = Ctx(compute_dtype=jnp.float32)

P1 = np.array([[5, 6, 7, 8, 9]], np.int32)
P2 = np.array([[3, 4, 5, 6, 2]], np.int32)
P3 = np.array([[9, 8, 7, 6, 5]], np.int32)
P4 = np.array([[2, 3, 9, 1, 4]], np.int32)


@pytest.fixture(scope="module")
def lm():
    rc = reduce_config(REGISTRY["gemma3-1b"])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    return rc, model, params


def _replicas(lm, n, **kw):
    """N engine replicas over ONE checkpoint on the default device —
    the routing control plane doesn't need device parallelism."""
    _, model, params = lm
    kw.setdefault("slots", 1)
    kw.setdefault("max_len", 32)
    return [ServeEngine(model, params, ctx=CTX, **kw) for _ in range(n)]


# ---------------------------------------------------------------------------
# mesh-spec parsing + mesh construction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec,want", [
    ("dp2,tp2", (2, 2)),
    ("tp4", (1, 4)),
    ("dp3", (3, 1)),
    ("tp2,dp3", (3, 2)),          # order-free
    (" dp2 , tp2 ", (2, 2)),      # whitespace tolerated
])
def test_parse_mesh_spec(spec, want):
    assert parse_mesh_spec(spec) == want


@pytest.mark.parametrize("bad", ["dp2,dp3", "pp2", "dp", "dp0", "2"])
def test_parse_mesh_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_mesh_spec(bad)


def test_tp_mesh_shape_and_device_bound():
    m = tp_mesh(1)
    assert m.axis_names == ("model",) and m.devices.shape == (1,)
    with pytest.raises(ValueError, match="host_platform_device_count"):
        tp_mesh(len(jax.devices()) + 1)


def test_router_needs_at_least_one_replica():
    with pytest.raises(ValueError, match="at least one replica"):
        ReplicaRouter([])


# ---------------------------------------------------------------------------
# routing control plane (real engines, single device)
# ---------------------------------------------------------------------------


def test_router_spreads_load_and_remaps_ids(lm):
    """Four submits over two 1-slot replicas alternate 0,1,0,1; the
    caller sees cluster-global ids and streams identical to a lone
    engine serving the same requests."""
    eng = _replicas(lm, 1)[0]
    sps = [SamplingParams(max_new_tokens=4, seed=i) for i in range(4)]
    want = {}
    for p, sp in zip((P1, P2, P3, P4), sps):
        rid = eng.submit({"tokens": p}, sp)
        want[rid] = eng.run_until_drained()[0].token_ids

    router = ReplicaRouter(_replicas(lm, 2))
    gids = [router.submit({"tokens": p}, sp)
            for p, sp in zip((P1, P2, P3, P4), sps)]
    assert gids == [0, 1, 2, 3]
    assert [router.replica_of(g) for g in gids] == [0, 1, 0, 1]
    outs = {o.request_id: o for o in router.run_until_drained()}
    assert sorted(outs) == gids
    for i, g in enumerate(gids):
        assert outs[g].token_ids == want[i], f"request {g} diverged"
        assert outs[g].finish_reason == "length"
    # bookkeeping drained with the requests
    assert router._owner == {} and all(m == {} for m in router._local)
    assert router.num_pending == router.num_active == 0


def test_abort_routes_to_owning_replica(lm):
    router = ReplicaRouter(_replicas(lm, 2))
    sp = SamplingParams(max_new_tokens=8)
    g0 = router.submit({"tokens": P1}, sp)           # replica 0, active
    g1 = router.submit({"tokens": P2}, sp)           # replica 1, active
    g2 = router.submit({"tokens": P3}, sp)           # replica 0, queued
    assert router._owner[g2][0] == 0
    assert router.replicas[0].num_pending == 1
    out = router.abort(g2)
    assert out.request_id == g2 and out.finish_reason == "abort"
    assert out.token_ids == []                       # never reached a slot
    assert router.replicas[0].num_pending == 0       # owner took the abort
    assert router.replicas[1].num_pending == 0
    assert router.abort(999) is None                 # unknown id
    outs = router.run_until_drained()
    assert sorted(o.request_id for o in outs) == [g0, g1]
    assert router.abort(g0) is None                  # already finished


def test_saturated_replica_failover_then_cluster_raise(lm):
    """Submission skips a saturated replica for the next-least-loaded
    one; the typed error resurfaces — with cluster totals — only when
    every replica rejects. Nothing already admitted is lost."""
    r0, r1 = _replicas(lm, 1, max_pending=1)[0], \
        _replicas(lm, 1, max_pending=2)[0]
    router = ReplicaRouter([r0, r1])
    sp = SamplingParams(max_new_tokens=3)
    gids = [router.submit({"tokens": p}, sp)
            for p in (P1, P2, P3, P4)]
    # placement so far: r0=[P1 active, P3 queued], r1=[P2 active,
    # P4 queued] — alternating by competition count
    assert [router.replica_of(g) for g in gids] == [0, 1, 0, 1]
    # 5th submit ties on load, tries r0 first (index), bounces off its
    # full queue, and fails over to r1's deeper queue
    g4 = router.submit({"tokens": P1}, sp)
    assert router._owner[g4][0] == 1
    assert r0.metrics().admission_rejections == 1
    # 6th: r0 and r1 both full -> the router re-raises with summed
    # pending/limit so callers can back off on cluster capacity
    with pytest.raises(EngineSaturated) as ei:
        router.submit({"tokens": P2}, sp)
    assert ei.value.pending == 3 and ei.value.limit == 3
    outs = router.run_until_drained()
    assert sorted(o.request_id for o in outs) == gids + [g4]
    assert all(o.finish_reason == "length" for o in outs)


def test_deadline_expires_on_backlogged_replica(lm):
    """A tight-deadline request queued behind a long generation expires
    on its owning replica while the other replica's work is untouched;
    the expiry shows up in the merged cluster metrics."""
    router = ReplicaRouter(_replicas(lm, 2))
    g_long = router.submit({"tokens": P1},
                           SamplingParams(max_new_tokens=24, eos_id=-1))
    g_other = router.submit({"tokens": P2},
                            SamplingParams(max_new_tokens=4, eos_id=-1))
    g_late = router.submit({"tokens": P3},
                           SamplingParams(max_new_tokens=4, eos_id=-1,
                                          deadline_ms=1.0))
    assert router._owner[g_late][0] == 0             # behind the long run
    outs = {o.request_id: o for o in router.run_until_drained()}
    assert outs[g_late].finish_reason == "deadline"
    assert outs[g_long].finish_reason == "length"
    assert outs[g_other].finish_reason == "length"
    m = router.metrics()
    assert m.deadline_expirations == 1
    assert router.replicas[0].metrics().deadline_expirations == 1
    assert router.replicas[1].metrics().deadline_expirations == 0


def test_priority_routes_past_lower_priority_backlog(lm):
    """A high-priority request counts only >=priority work as
    competition: it routes to the replica whose backlog it outranks,
    not the emptier-looking one holding peer-priority work."""
    router = ReplicaRouter(_replicas(lm, 2, slots=2))
    lo = SamplingParams(max_new_tokens=4, priority=0)
    hi = SamplingParams(max_new_tokens=4, priority=1)
    router.submit({"tokens": P1}, lo)                # r0
    router.submit({"tokens": P2}, lo)                # r1
    router.submit({"tokens": P3}, lo)                # r0 (index tiebreak)
    g_hi = router.submit({"tokens": P4}, hi)
    # r0 carries 2 low-priority requests, r1 carries 1 — but neither
    # competes at priority 1, so the tiebreak falls through to total
    # backlog and the high-priority request lands on r1
    assert router._owner[g_hi][0] == 1
    router.run_until_drained()


def test_stream_request_unsupported_at_router(lm):
    router = ReplicaRouter(_replicas(lm, 2))
    with pytest.raises(NotImplementedError, match="on_token"):
        router.stream_request({"tokens": P1})


# ---------------------------------------------------------------------------
# fairness: no replica starves under mixed priorities (seeded property)
# ---------------------------------------------------------------------------


class _StubEngine:
    """ServeEngine stand-in for routing-policy properties: live-count
    bookkeeping only, no model, no JAX — hypothesis can afford
    thousands of submits."""

    def __init__(self, max_pending=None):
        self.max_pending = max_pending
        self.num_active = 0
        self._queue = []
        self._next = 0

    @property
    def num_pending(self):
        return len(self._queue)

    def submit(self, request, params=None, on_token=None):
        if self.max_pending is not None \
                and len(self._queue) >= self.max_pending:
            raise EngineSaturated(len(self._queue), self.max_pending)
        lid = self._next
        self._next += 1
        self._queue.append(lid)
        return lid


def _check_fairness(priorities, n_rep):
    """Mixed-priority arrival stream: every placement matches the
    policy's least-competition order, no replica starves, and a
    uniform-priority stream balances perfectly (within +-1)."""
    router = ReplicaRouter([_StubEngine() for _ in range(n_rep)])
    for i, p in enumerate(priorities):
        want = router._order(p)[0]
        gid = router.submit({"tokens": [i]},
                            SamplingParams(max_new_tokens=1, priority=p))
        assert router._owner[gid][0] == want
    loads = [e.num_pending for e in router.replicas]
    assert sum(loads) == len(priorities)
    assert min(loads) >= 1                 # len(priorities) >= n_rep: no
    #                                        replica starves, whatever the
    #                                        priority mix
    if len(set(priorities)) == 1:
        assert max(loads) - min(loads) <= 1


@pytest.mark.parametrize("priorities,n_rep", [
    ([0] * 12, 3),                             # uniform: perfect balance
    ([3, 0, 0, 0, 3, 0, 0, 0, 3], 2),          # sparse high priorities
    ([0, 1, 2, 3] * 4, 4),                     # rotating mix
    ([2, 2, 1, 0, 0, 0, 0, 3], 3),             # front-loaded urgency
])
def test_router_fairness_fixed_streams(priorities, n_rep):
    """Fixed-stream arm of the fairness property — always runs, even
    where hypothesis is unavailable."""
    _check_fairness(priorities, n_rep)


def test_router_fairness_no_replica_starves():
    """Property: under ANY mixed-priority arrival stream, placement
    follows the least-competition order and no replica starves."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=80, deadline=None, derandomize=True)
    @hyp.given(priorities=st.lists(st.integers(min_value=0, max_value=3),
                                   min_size=8, max_size=40),
               n_rep=st.integers(min_value=2, max_value=4))
    def check(priorities, n_rep):
        _check_fairness(priorities, n_rep)

    check()


# ---------------------------------------------------------------------------
# metric merging: counters sum, percentiles come from merged histograms
# ---------------------------------------------------------------------------


def _snap(**over):
    base = {f.name: 0 for f in dataclasses.fields(EngineMetrics)}
    base.update(over)
    return EngineMetrics(**base)


def test_merge_metrics_sums_and_reweights():
    a = _snap(decode_steps=10, synced_tokens=40, decode_syncs=10,
              preemptions=1, occupancy=0.5, kv_cache_bytes=100)
    b = _snap(decode_steps=30, synced_tokens=30, decode_syncs=10,
              preemptions=2, occupancy=0.9, kv_cache_bytes=300)
    ttft, tpot = Histogram(), Histogram()
    for v in (1.0, 2.0, 100.0):
        ttft.record(v)
        tpot.record(v / 10)
    m = merge_metrics([a, b], ttft_hist=ttft, tpot_hist=tpot)
    assert m.decode_steps == 40 and m.synced_tokens == 70
    assert m.preemptions == 3 and m.kv_cache_bytes == 400
    # ratio recomputed from summed counters, not averaged
    assert m.mean_tokens_per_sync == pytest.approx(70 / 20)
    # occupancy: decode_steps-weighted mean (pooled ratio for
    # homogeneous replicas)
    assert m.occupancy == pytest.approx((0.5 * 10 + 0.9 * 30) / 40)
    # percentiles from the merged histogram (bucket upper edges)
    assert m.ttft_p95_ms == pytest.approx(ttft.percentile(95.0))
    assert merge_metrics([a]).ttft_p95_ms == 0.0     # no hist, no claim
    with pytest.raises(ValueError, match="at least one"):
        merge_metrics([])


def test_router_merged_histograms_match_replica_sums(lm):
    router = ReplicaRouter(_replicas(lm, 2))
    for i, p in enumerate((P1, P2, P3, P4)):
        router.submit({"tokens": p}, SamplingParams(max_new_tokens=3,
                                                    seed=i))
    router.run_until_drained()
    merged = router.merged_latency_histograms()
    for name in ("ttft_ms", "tpot_ms"):
        per = [e.latency_histograms()[name] for e in router.replicas]
        assert merged[name].count == sum(h.count for h in per) == 4
        assert merged[name].counts == [
            sum(h.counts[i] for h in per)
            for i in range(merged[name].n_buckets)]
        # merging into a fresh accumulator left the sources alone
        assert all(h.count == 2 for h in per)
    m = router.metrics()
    assert m.synced_tokens == sum(
        e.metrics().synced_tokens for e in router.replicas)
    assert m.ttft_p95_ms == pytest.approx(merged["ttft_ms"].percentile(95.0),
                                          abs=1e-3)
    router.reset_metrics()
    assert router.metrics().synced_tokens == 0
    assert router.merged_latency_histograms()["ttft_ms"].count == 0


def test_cluster_prometheus_has_merged_and_labelled_sections(lm):
    router = ReplicaRouter(_replicas(lm, 2))
    router.submit({"tokens": P1}, SamplingParams(max_new_tokens=3))
    router.run_until_drained()
    text = router.prometheus()
    assert "# TYPE repro_cluster_decode_syncs counter" in text
    assert "# TYPE repro_cluster_ttft_ms histogram" in text
    for i in range(2):
        assert f'repro_cluster_replica_synced_tokens{{replica="{i}"}}' \
            in text
    # one TYPE declaration per family, however many replicas
    assert text.count(
        "# TYPE repro_cluster_replica_synced_tokens counter") == 1


def test_render_prometheus_labeled_groups_families():
    rows = [({"replica": "0"}, _snap(decode_syncs=3)),
            ({"replica": "1"}, _snap(decode_syncs=5))]
    text = render_prometheus_labeled(rows, prefix="t")
    lines = text.splitlines()
    i = lines.index("# TYPE t_decode_syncs counter")
    assert lines[i + 1] == 't_decode_syncs{replica="0"} 3'
    assert lines[i + 2] == 't_decode_syncs{replica="1"} 5'
    # gauges keep their gauge type under labels too
    assert "# TYPE t_kv_cache_bytes gauge" in text


# ---------------------------------------------------------------------------
# single-device cluster parity through the eval suite's grid gate
# ---------------------------------------------------------------------------


def test_deploy_replicas_grid_matches_single_engine():
    """deploy_replicas on one device (no meshes) must serve the eval
    suite's greedy pair grid identically to a lone deploy — the
    routed-parity gate the 8-device subprocess tests rerun sharded."""
    kwargs = dict(slots=2, max_len=16, ctx=CTX, init_seed=0, paged=True,
                  page_size=4, horizon=4)
    single = deploy("nllb600m", "int8", smoke=True, **kwargs)
    cluster = deploy_replicas("nllb600m", "int8", replicas=2, smoke=True,
                              **kwargs)
    assert isinstance(cluster.engine, ReplicaRouter)
    assert cluster.engine.max_len == single.engine.max_len
    assert_serving_equivalence(
        cluster, single, pair_list=[("hin", "eng"), ("eng", "hin")],
        label="dp2 router", n_sent=2, max_new_tokens=6)


# ---------------------------------------------------------------------------
# 8-device parity: tensor-parallel engines and routed clusters
# (subprocess: conftest pins this process to one CPU device)
# ---------------------------------------------------------------------------


def _run_forced_8dev(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"subprocess failed:\n{r.stderr[-3000:]}"
    return r.stdout


_PARITY_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp

    from repro.configs import REGISTRY, reduce_config
    from repro.data import SyntheticTranslation
    from repro.models import Ctx
    from repro.serving import SamplingParams, deploy

    assert len(jax.devices()) == 8, jax.devices()
    cfg = reduce_config(REGISTRY["nllb600m"])
    ctx = Ctx(compute_dtype=jnp.float32)
    ds = SyntheticTranslation(cfg.vocab_size, cfg.enc_len, seed=0,
                              languages=("hin", "eng", "ita"))
    src = jnp.asarray(ds.sample(3)["src_tokens"])
    GREEDY = SamplingParams(max_new_tokens=8)
    SAMPLED = SamplingParams(max_new_tokens=8, temperature=0.8, top_k=8,
                             seed=7)

    def grids(pipe):
        return (
            [(o.token_ids, o.finish_reason)
             for o in pipe.translate(src, "ita", GREEDY)],
            [(o.token_ids, o.finish_reason)
             for o in pipe.translate(src, "hin", SAMPLED)])

    def common(paged, K):
        return dict(slots=2, max_len=16, params=None, ctx=ctx,
                    paged=paged, page_size=4, horizon=K, init_seed=0)
"""


@pytest.mark.slow
def test_tensor_parallel_streams_match_single_device():
    """deploy(mesh=tp_mesh(K)) parity grid: dense/paged x horizon 1/16,
    greedy + seeded sampling, tp2 everywhere plus a tp4 widest case —
    token-for-token against the unmeshed single-device engine."""
    out = _run_forced_8dev(_PARITY_PRELUDE + """
    from repro.cluster import tp_mesh

    cases = 0
    for paged in (False, True):
        for K in (1, 16):
            base = deploy(cfg, "int8", **common(paged, K))
            ref = grids(base)
            widths = (2, 4) if (paged and K == 16) else (2,)
            for tp in widths:
                pipe = deploy(cfg, "int8", mesh=tp_mesh(tp),
                              **common(paged, K))
                assert grids(pipe) == ref, (paged, K, tp)
                print(f"OK paged={paged} K={K} tp={tp}")
                cases += 1
    print("CASES", cases)
    """)
    assert "CASES 5" in out


@pytest.mark.slow
def test_replica_router_streams_match_single_device():
    """deploy_replicas parity grid: dp2 routed clusters (tp1 pinned
    meshes, plus the composed dp2,tp2 stack) serve the single-device
    streams exactly, dense/paged x horizon 1/16, greedy + sampled;
    merged metrics stay consistent with per-replica sums."""
    out = _run_forced_8dev(_PARITY_PRELUDE + """
    from repro.cluster import deploy_replicas

    cases = 0
    for paged in (False, True):
        for K in (1, 16):
            base = deploy(cfg, "int8", **common(paged, K))
            ref = grids(base)
            stacks = ((2, 1), (2, 2)) if (paged and K == 16) else ((2, 1),)
            for dp, tp in stacks:
                pipe = deploy_replicas(cfg, "int8", replicas=dp, tp=tp,
                                       **common(paged, K))
                assert grids(pipe) == ref, (paged, K, dp, tp)
                router = pipe.engine
                m = router.metrics()
                per = [e.metrics() for e in router.replicas]
                assert m.synced_tokens == sum(p.synced_tokens
                                              for p in per)
                h = router.merged_latency_histograms()["ttft_ms"]
                assert h.count == sum(
                    e.latency_histograms()["ttft_ms"].count
                    for e in router.replicas)
                prom = router.prometheus()
                assert "repro_cluster_ttft_ms_bucket" in prom
                assert 'repro_cluster_replica_occupancy{replica="1"}' \
                    in prom
                print(f"OK paged={paged} K={K} dp={dp} tp={tp}")
                cases += 1
    print("CASES", cases)
    """)
    assert "CASES 5" in out
