"""Serving engine: request-level API, sampling, continuous batching.

Covers the legacy single-shot wrappers (greedy_generate / translate,
back-compat), the scheduler-owned ServeEngine (submit / step /
run_until_drained, EOS-aware retirement, mixed per-slot SamplingParams,
prefill-length bucketing), the deploy() pipeline, and the horizon-fused
decode path (horizon=K must be token-for-token identical to horizon=1
for dense and paged caches, greedy and seeded sampling, mixed per-slot
params, mid-stream admission, and abort).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduce_config
from repro.data import LANG_CODES
from repro.models import Ctx, build_model
from repro.serving import (SamplingParams, ServeEngine, deploy,
                           greedy_generate, translate)

CTX = Ctx(compute_dtype=jnp.float32)


def _lm(name="gemma3-1b"):
    rc = reduce_config(REGISTRY[name])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    return rc, model, params


# ---------------------------------------------------------------------------
# legacy wrapper back-compat
# ---------------------------------------------------------------------------

# the single-shot wrappers are deprecated in favor of the streaming
# pipeline surface; tests covering them opt out of the CI pinned leg's
# -W error::DeprecationWarning (test marks outrank the command line)
legacy = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@legacy
def test_greedy_generate_deterministic():
    rc, model, params = _lm()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, rc.vocab_size)
    out1, _ = greedy_generate(model, CTX, params, {"tokens": toks}, steps=5,
                              max_len=16)
    out2, _ = greedy_generate(model, CTX, params, {"tokens": toks}, steps=5,
                              max_len=16)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 5)


@legacy
def test_translate_api_shapes():
    rc = reduce_config(REGISTRY["nllb600m"])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    src = jax.random.randint(jax.random.PRNGKey(1), (3, rc.enc_len), 0,
                             rc.vocab_size)
    toks = translate(model, CTX, params, src, LANG_CODES["ita"], steps=6,
                     max_len=16)
    assert toks.shape == (3, 6)
    assert int(toks.min()) >= 0 and int(toks.max()) < rc.vocab_size


@legacy
def test_translate_overflow_raises():
    rc = reduce_config(REGISTRY["nllb600m"])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    src = jax.random.randint(jax.random.PRNGKey(1), (1, rc.enc_len), 0,
                             rc.vocab_size)
    # 1 (lang-code prompt) + 8 steps > max_len=8: must raise, not wrap
    with pytest.raises(ValueError, match="max_len"):
        translate(model, CTX, params, src, LANG_CODES["ita"], steps=8,
                  max_len=8)


@legacy
def test_int8_kv_generation_tracks_bf16():
    rc, model, params = _lm("qwen2.5-14b")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, rc.vocab_size)
    g16, _ = greedy_generate(model, CTX, params, {"tokens": toks}, steps=4,
                             max_len=16, kv_dtype="bf16")
    g8, _ = greedy_generate(model, CTX, params, {"tokens": toks}, steps=4,
                            max_len=16, kv_dtype="int8")
    # argmax ids may deviate eventually; first step must agree on a
    # random-init model with typical logit gaps
    assert int(g16[0, 0]) == int(g8[0, 0])


@legacy
def test_continuous_batching_matches_single_stream():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=3, max_len=24, ctx=CTX)
    p1 = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, rc.vocab_size)
    p2 = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, rc.vocab_size)
    s1 = eng.add_request({"tokens": p1}, gen_tokens=5)
    s2 = eng.add_request({"tokens": p2}, gen_tokens=5)
    while eng.slots[s1].active or eng.slots[s2].active:
        eng.tick()
    ref1, _ = greedy_generate(model, CTX, params, {"tokens": p1}, steps=5,
                              max_len=24)
    ref2, _ = greedy_generate(model, CTX, params, {"tokens": p2}, steps=5,
                              max_len=24)
    assert eng.result(s1) == list(np.asarray(ref1[0]))
    assert eng.result(s2) == list(np.asarray(ref2[0]))


def test_slot_reuse_after_completion():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX)
    p = jax.random.randint(jax.random.PRNGKey(3), (1, 4), 0, rc.vocab_size)
    s = eng.add_request({"tokens": p}, gen_tokens=2)
    while eng.slots[s].active:
        eng.tick()
    assert eng.free_slot() == s          # slot released
    s2 = eng.add_request({"tokens": p}, gen_tokens=2)
    while eng.slots[s2].active:
        eng.tick()
    assert eng.result(s2) == eng.result(s)   # cache fully re-primed


# ---------------------------------------------------------------------------
# request-level API
# ---------------------------------------------------------------------------

def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=0)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


def test_submit_rejects_overflowing_request():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=8, ctx=CTX)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 6), 0, rc.vocab_size)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit({"tokens": p}, SamplingParams(max_new_tokens=4))


@legacy
def test_eos_stops_generation_and_reports_reason():
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, rc.vocab_size)
    eng = ServeEngine(model, params, slots=1, max_len=24, ctx=CTX)
    rid = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=6))
    ref = {o.request_id: o for o in eng.run_until_drained()}[rid]
    assert ref.finish_reason == "length"
    # pick a token the greedy stream actually emits as the EOS id
    eos = ref.token_ids[2]
    pos = ref.token_ids.index(eos)       # first occurrence may be earlier
    eng2 = ServeEngine(model, params, slots=1, max_len=24, ctx=CTX)
    rid = eng2.submit({"tokens": p},
                      SamplingParams(max_new_tokens=6, eos_id=eos))
    out = {o.request_id: o for o in eng2.run_until_drained()}[rid]
    assert out.finish_reason == "eos"
    assert out.token_ids == ref.token_ids[:pos + 1]   # EOS included, then stop
    # wrapper: same EOS id masks every position after the stop
    toks, _ = greedy_generate(model, CTX, params, {"tokens": p}, steps=6,
                              max_len=24, eos_id=eos)
    assert toks.shape == (1, 6)
    assert list(np.asarray(toks[0, :pos + 1])) == ref.token_ids[:pos + 1]
    assert all(int(t) == eos for t in np.asarray(toks[0, pos + 1:]))


def test_temperature_zero_equals_greedy():
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(4), (1, 5), 0, rc.vocab_size)
    eng = ServeEngine(model, params, slots=2, max_len=16, ctx=CTX)
    r0 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=4))
    r1 = eng.submit({"tokens": p},
                    SamplingParams(temperature=0.0, top_k=3, top_p=0.5,
                                   max_new_tokens=4, seed=123))
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert outs[r0].token_ids == outs[r1].token_ids


def test_sampling_seed_determinism():
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(5), (1, 5), 0, rc.vocab_size)
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.9,
                        max_new_tokens=5, seed=11)

    def run(slots):
        eng = ServeEngine(model, params, slots=slots, max_len=16, ctx=CTX)
        rid = eng.submit({"tokens": p}, sp)
        return {o.request_id: o for o in eng.run_until_drained()}[rid]

    a, b = run(1), run(1)
    assert a.token_ids == b.token_ids          # same seed -> same stream
    assert a.finish_reason == "length"
    # top_k=1 collapses sampling to greedy regardless of temperature/seed
    eng = ServeEngine(model, params, slots=2, max_len=16, ctx=CTX)
    rg = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=5))
    rk = eng.submit({"tokens": p},
                    SamplingParams(temperature=1.3, top_k=1,
                                   max_new_tokens=5, seed=77))
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert outs[rg].token_ids == outs[rk].token_ids


def test_mixed_sampling_params_one_batch():
    """Greedy and sampled slots share one step fn; each stream is exactly
    what it would be served alone (slot placement doesn't leak)."""
    rc, model, params = _lm()
    p1 = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, rc.vocab_size)
    p2 = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, rc.vocab_size)
    sp_samp = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=5,
                             seed=3)

    def solo(prompt, sp):
        eng = ServeEngine(model, params, slots=1, max_len=24, ctx=CTX)
        rid = eng.submit({"tokens": prompt}, sp)
        return {o.request_id: o for o in eng.run_until_drained()}[rid]

    ref_g = solo(p1, SamplingParams(max_new_tokens=5))
    ref_s = solo(p2, sp_samp)

    eng = ServeEngine(model, params, slots=3, max_len=24, ctx=CTX)
    rg = eng.submit({"tokens": p1}, SamplingParams(max_new_tokens=5))
    rs = eng.submit({"tokens": p2}, sp_samp)
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert outs[rg].token_ids == ref_g.token_ids
    assert outs[rs].token_ids == ref_s.token_ids
    # greedy + sampled slots ran under ONE compiled step executable:
    # SamplingParams enter as traced arrays, never as static args
    cache_size = getattr(eng._step_fn, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 1


def test_engine_queue_overcommit_and_stats():
    """More requests than slots: the engine queues and drains them all."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=2, max_len=16, ctx=CTX)
    ids = []
    for i in range(5):
        p = jax.random.randint(jax.random.PRNGKey(i), (1, 4), 0,
                               rc.vocab_size)
        ids.append(eng.submit({"tokens": p},
                              SamplingParams(max_new_tokens=3, seed=i)))
    assert eng.num_pending == 3 and eng.num_active == 2
    outs = eng.run_until_drained()
    assert sorted(o.request_id for o in outs) == ids
    for o in outs:
        assert o.finish_reason == "length"
        assert o.num_generated == 3
        assert o.stats.prompt_len == 4
        assert o.stats.finished_s >= o.stats.first_token_s >= o.stats.arrival_s


def test_request_stats_stamp_admission_and_queue_wait():
    """Every output carries when its request was admitted and how long
    it waited queued: a request admitted at once waited next to
    nothing, one queued behind a full engine waited until a slot
    freed, and one aborted while queued waited until the abort."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 4), 0, rc.vocab_size)
    first = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=3))
    second = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=3))
    third = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=3))
    gone = eng.abort(third)
    outs = {o.request_id: o for o in eng.run_until_drained()}
    a, b = outs[first], outs[second]
    for o in (a, b):
        st = o.stats
        assert st.arrival_s <= st.admitted_s <= st.first_token_s
        assert o.queue_ms == pytest.approx(
            (st.admitted_s - st.arrival_s) * 1e3)
    # the second waited for the first to finish and free the one slot
    assert b.stats.admitted_s >= a.stats.finished_s
    assert b.queue_ms > a.queue_ms >= 0.0
    assert gone.stats.admitted_s == 0.0
    assert gone.queue_ms == pytest.approx(
        (gone.stats.finished_s - gone.stats.arrival_s) * 1e3)


def test_abort_request():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 4), 0, rc.vocab_size)
    r1 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=8))
    r2 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=8))
    assert eng.num_pending == 1              # r2 waits behind r1
    o2 = eng.abort(r2)
    assert o2.finish_reason == "abort" and o2.token_ids == []
    o1 = eng.abort(r1)
    assert o1.finish_reason == "abort" and len(o1.token_ids) >= 1
    assert eng.run_until_drained() == []
    assert eng.abort(999) is None


def test_prefill_length_bucketing_bounds_compiles():
    """Distinct prompt lengths must not each trigger a fresh prefill
    compile: lengths bucket to powers of two (here 4 and 8)."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=32, ctx=CTX)
    for i, plen in enumerate((3, 4, 5, 6, 7, 8)):
        p = jax.random.randint(jax.random.PRNGKey(i), (1, plen), 0,
                               rc.vocab_size)
        eng.submit({"tokens": p}, SamplingParams(max_new_tokens=2))
        eng.run_until_drained()
    assert eng.prefill_compiles == 2
    # the jit cache agrees with the engine's own accounting
    cache_size = getattr(eng._prefill_fn, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 2


def test_bucketed_prefill_matches_exact_prefill():
    """Right-padding + lengths masking must not change the decoded
    stream (pos=-1 slots are masked out of attention)."""
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(9), (1, 5), 0, rc.vocab_size)

    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX)
    rid = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=4))
    bucketed = {o.request_id: o for o in eng.run_until_drained()}[rid]

    # exact-length reference: batched prefill with no padding
    cache = model.init_cache(1, 16, "bf16")
    cache, logits = model.prefill(CTX, params, cache, {"tokens": p})
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    ref = [int(tok[0, 0])]
    for _ in range(3):
        cache, logits = model.decode_step(CTX, params, tok, cache)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        ref.append(int(tok[0, 0]))
    assert bucketed.token_ids == ref


# ---------------------------------------------------------------------------
# deploy() pipeline
# ---------------------------------------------------------------------------

@legacy
def test_deploy_translate_pipeline():
    pipe = deploy("nllb600m", "int4", slots=2, max_len=16, smoke=True)
    assert pipe.compression > 2.0            # int4 shrinks the checkpoint
    src = jax.random.randint(jax.random.PRNGKey(1), (3, pipe.cfg.enc_len), 0,
                             pipe.cfg.vocab_size)
    outs = pipe.translate(src, "ita", SamplingParams(max_new_tokens=6))
    assert len(outs) == 3
    assert [o.num_generated for o in outs] == [6, 6, 6]
    assert all(o.finish_reason == "length" for o in outs)
    # wrapper path and pipeline path agree (same engine underneath)
    toks = translate(pipe.model, pipe.ctx, pipe.params, src,
                     LANG_CODES["ita"], steps=6, max_len=16, kv_dtype="int8")
    assert [list(np.asarray(r)) for r in toks] == [o.token_ids for o in outs]


# ---------------------------------------------------------------------------
# horizon-fused decode
# ---------------------------------------------------------------------------

def _outputs_by_id(eng, ids):
    outs = {o.request_id: o for o in eng.run_until_drained()}
    return [outs[i] for i in ids]


def _assert_equiv(base, got, K):
    for b, g in zip(base, got):
        assert g.token_ids == b.token_ids, \
            f"horizon={K}: {g.token_ids} != {b.token_ids}"
        assert g.finish_reason == b.finish_reason
        assert g.num_generated == b.num_generated == g.stats.new_tokens


def test_horizon_equivalence_dense_mixed_params():
    """horizon=K token streams, finish reasons, and stats must match
    horizon=1 exactly — greedy and seeded top-p slots side by side."""
    rc, model, params = _lm()
    p1 = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, rc.vocab_size)
    p2 = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, rc.vocab_size)
    sp_g = SamplingParams(max_new_tokens=7)
    sp_s = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=5,
                          seed=3)

    def run(K):
        eng = ServeEngine(model, params, slots=2, max_len=24, ctx=CTX,
                          horizon=K)
        ids = [eng.submit({"tokens": p1}, sp_g),
               eng.submit({"tokens": p2}, sp_s)]
        return _outputs_by_id(eng, ids)

    base = run(1)
    for K in (4, 16):
        _assert_equiv(base, run(K), K)


def test_horizon_equivalence_dense_eos():
    """EOS emitted mid-horizon retires the slot at the same position and
    with the same finish reason as per-token decode."""
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(7), (1, 5), 0, rc.vocab_size)

    def run(K, eos=None):
        eng = ServeEngine(model, params, slots=1, max_len=24, ctx=CTX,
                          horizon=K)
        ids = [eng.submit({"tokens": p},
                          SamplingParams(max_new_tokens=8, eos_id=eos))]
        return _outputs_by_id(eng, ids)

    ref = run(1)[0]
    eos = ref.token_ids[2]              # a token the stream actually emits
    base = run(1, eos)
    assert base[0].finish_reason == "eos"
    for K in (4, 16):
        _assert_equiv(base, run(K, eos), K)


def test_horizon_equivalence_paged():
    """Paged engine (block tables static across the horizon): fused and
    per-token decode agree for greedy + sampled slots, and every page
    returns to the pool."""
    pipes = {}
    for K in (1, 4, 16):
        pipes[K] = deploy("gemma3-1b", "int8", slots=3, max_len=32,
                          smoke=True, paged=True, page_size=4, horizon=K)
    cfg = pipes[1].cfg
    p1 = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab_size)
    p2 = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab_size)
    sp_g = SamplingParams(max_new_tokens=6)
    sp_s = SamplingParams(temperature=0.7, top_k=8, max_new_tokens=5, seed=11)

    def run(K):
        eng = pipes[K].engine
        ids = [eng.submit({"tokens": p1}, sp_g),
               eng.submit({"tokens": p2}, sp_s)]
        outs = _outputs_by_id(eng, ids)
        assert eng.allocator.pages_in_use == 0      # full reclaim
        return outs

    base = run(1)
    for K in (4, 16):
        _assert_equiv(base, run(K), K)


def test_horizon_equivalence_encdec_midstream_admission():
    """A request submitted between horizons (continuous batching refill)
    must decode the same stream as under per-token admission."""
    def run(K):
        pipe = deploy("nllb600m", "int8", slots=2, max_len=16, smoke=True,
                      paged=True, page_size=4, horizon=K)
        cfg = pipe.cfg
        srcs = [jax.random.randint(jax.random.PRNGKey(i), (1, cfg.enc_len),
                                   0, cfg.vocab_size) for i in range(3)]
        tgt = jnp.full((1, 1), 8, jnp.int32)
        eng = pipe.engine
        sp = SamplingParams(temperature=0.6, top_p=0.9, max_new_tokens=6,
                            seed=5)
        ids = [eng.submit({"src_tokens": srcs[0], "tgt_in": tgt}, sp),
               eng.submit({"src_tokens": srcs[1], "tgt_in": tgt}, sp)]
        early = eng.step()   # at large K a request can finish right here
        ids.append(eng.submit({"src_tokens": srcs[2], "tgt_in": tgt}, sp))
        outs = {o.request_id: o for o in early + eng.run_until_drained()}
        return [outs[i] for i in ids]

    base = run(1)
    for K in (4, 8):
        _assert_equiv(base, run(K), K)


def test_horizon_one_is_legacy_path():
    """horizon=1 (explicit or default) never builds a fused scan — the
    back-compat guarantee is the original executable, not a K=1 scan."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 4), 0, rc.vocab_size)
    eng.submit({"tokens": p}, SamplingParams(max_new_tokens=3))
    eng.run_until_drained()
    assert eng.horizon == 1 and eng._horizon_fns == {}
    with pytest.raises(ValueError, match="horizon"):
        eng.step(horizon=0)
    with pytest.raises(ValueError, match="horizon"):
        ServeEngine(model, params, slots=1, max_len=16, ctx=CTX, horizon=0)


def test_horizon_decode_syncs_metric():
    """One request needing 8 decode tokens: 8 syncs per-token, 1 sync at
    horizon=8; mean_tokens_per_sync reports the fusion win."""
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, rc.vocab_size)
    sp = SamplingParams(max_new_tokens=9)    # 1 prefill + 8 decode tokens

    def syncs(K):
        eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                          horizon=K)
        eng.submit({"tokens": p}, sp)
        eng.run_until_drained()
        return eng.decode_syncs, eng.mean_tokens_per_sync

    assert syncs(1) == (8, 1.0)
    assert syncs(8) == (1, 8.0)
    # reset_metrics zeroes the sync counters alongside occupancy
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX, horizon=8)
    eng.submit({"tokens": p}, sp)
    eng.run_until_drained()
    eng.reset_metrics()
    assert eng.decode_syncs == 0 and eng.mean_tokens_per_sync == 0.0


def test_horizon_abort_truncates_and_frees_pages_once():
    """Abort after a partial horizon: tokens truncate at the last synced
    position, the page chain is freed exactly once (the strict allocator
    raises on double-free), and the engine keeps serving."""
    pipe = deploy("gemma3-1b", "int8", slots=2, max_len=32, smoke=True,
                  paged=True, page_size=4, horizon=4)
    eng = pipe.engine
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 5), 0,
                           pipe.cfg.vocab_size)
    rid = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=20))
    eng.step()                            # admit + one fused horizon of 4
    assert eng.allocator.pages_in_use > 0
    out = eng.abort(rid)
    assert out.finish_reason == "abort"
    assert out.num_generated == 5         # 1 prefill + 4 synced tokens
    assert out.stats.new_tokens == 5
    assert eng.allocator.pages_in_use == 0      # chain freed, exactly once
    assert eng.abort(rid) is None               # idempotent, no double free
    rid2 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=6))
    outs = eng.run_until_drained()
    assert [o.request_id for o in outs] == [rid2]
    assert outs[0].num_generated == 6
    assert eng.allocator.pages_in_use == 0


def test_deploy_horizon_and_impl_knobs():
    """deploy() threads horizon into the engine and kernel routes into
    the pipeline Ctx; invalid routes fail fast."""
    pipe = deploy("gemma3-1b", "int8", slots=1, max_len=16, smoke=True,
                  horizon=4, matmul_impl="xla", paged_attn_impl="gather")
    assert pipe.engine.horizon == 4
    assert pipe.ctx.matmul_impl == "xla"
    assert pipe.ctx.paged_attn_impl == "gather"
    with pytest.raises(ValueError, match="matmul_impl"):
        deploy("gemma3-1b", "int8", smoke=True, matmul_impl="cuda")
    with pytest.raises(ValueError, match="paged_attn_impl"):
        deploy("gemma3-1b", "int8", smoke=True, paged_attn_impl="flash")


def test_deploy_generate_lm():
    pipe = deploy("gemma3-1b", "int8", slots=2, max_len=16, smoke=True)
    prompts = [jnp.arange(4) % pipe.cfg.vocab_size,
               jnp.arange(6) % pipe.cfg.vocab_size]
    outs = pipe.generate(prompts, SamplingParams(max_new_tokens=4))
    assert [o.num_generated for o in outs] == [4, 4]
    assert outs[0].request_id < outs[1].request_id   # input order
    with pytest.raises(TypeError, match="enc-dec"):
        pipe.translate(jnp.ones((1, 4), jnp.int32), "ita")
