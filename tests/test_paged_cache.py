"""Paged KV serving: allocator invariants, paged==dense decode, reclaim.

The headline contract of the paged engine (ISSUE 2): block-paged decode
is token-for-token identical to the dense-cache engine at bf16 and int8
KV, pages are reclaimed on EOS/abort with zero leaks, mixed source
lengths share one enc-dec engine, and continuous paged admission keeps
occupancy at or above the dense baseline.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, reduce_config
from repro.models import Ctx, build_model
from repro.serving import PageAllocator, SamplingParams, ServeEngine, deploy
from repro.serving.paged_cache import pages_needed

CTX = Ctx(compute_dtype=jnp.float32)


def _lm(name="gemma3-1b"):
    rc = reduce_config(REGISTRY[name])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    return rc, model, params


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_allocator_basic_invariants():
    a = PageAllocator(9)                     # 8 usable, page 0 reserved
    assert a.num_free == 8 and a.pages_in_use == 0
    c1 = a.alloc_chain(3)
    c2 = a.alloc_chain(2)
    assert len(set(c1) | set(c2)) == 5       # disjoint chains
    assert 0 not in c1 + c2                  # trash page never handed out
    assert a.pages_in_use == 5 and a.num_free == 3
    a.free_chain(c1)
    a.check()
    assert a.num_free == 6
    c3 = a.alloc_chain(6)
    assert set(c3) & set(c1) == set(c1)      # freed pages are reusable
    with pytest.raises(MemoryError, match="exhausted"):
        a.alloc_chain(1)


def test_allocator_double_free_raises():
    a = PageAllocator(5)
    c = a.alloc_chain(2)
    a.free_chain(c)
    with pytest.raises(ValueError, match="free"):
        a.free_chain(c)
    with pytest.raises(ValueError, match="free"):
        a.free_chain([4])                    # never allocated
    with pytest.raises(ValueError, match="duplicate"):
        a.free_chain(a.alloc_chain(2) * 2)


def test_pages_needed():
    assert pages_needed(0, 4) == 0
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2


# ---------------------------------------------------------------------------
# paged engine == dense engine, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_matches_dense_token_for_token(kv):
    rc, model, params = _lm()
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (1, 3 + i % 5), 0,
                                  rc.vocab_size) for i in range(6)]
    sp = SamplingParams(max_new_tokens=5)

    dense = ServeEngine(model, params, slots=2, max_len=16, kv_dtype=kv,
                        ctx=CTX)
    ids_d = [dense.submit({"tokens": p}, sp) for p in prompts]
    outs_d = {o.request_id: o for o in dense.run_until_drained()}

    paged = ServeEngine(model, params, slots=2, max_len=16, kv_dtype=kv,
                        ctx=CTX, paged=True, page_size=4)
    ids_p = [paged.submit({"tokens": p}, sp) for p in prompts]
    outs_p = {o.request_id: o for o in paged.run_until_drained()}

    for a, b in zip(ids_d, ids_p):
        assert outs_d[a].token_ids == outs_p[b].token_ids
    assert paged.allocator.pages_in_use == 0   # everything reclaimed
    paged.allocator.check()


def test_paged_encdec_matches_dense_int8():
    pipe_p = deploy("nllb600m", "int8", slots=2, max_len=16, smoke=True,
                    paged=True, page_size=4)
    pipe_d = deploy("nllb600m", "int8", slots=2, max_len=16, smoke=True)
    cfg = pipe_p.cfg
    src = jax.random.randint(jax.random.PRNGKey(1), (3, cfg.enc_len), 0,
                             cfg.vocab_size)
    sp = SamplingParams(max_new_tokens=6)
    outs_p = pipe_p.translate(src, "ita", sp)
    outs_d = pipe_d.translate(src, "ita", sp)
    assert [o.token_ids for o in outs_p] == [o.token_ids for o in outs_d]
    assert pipe_p.engine.allocator.pages_in_use == 0


def test_paged_sampled_stream_matches_dense():
    """Same seed, same stream — independent of paging and slot layout."""
    rc, model, params = _lm()
    p = jax.random.randint(jax.random.PRNGKey(5), (1, 5), 0, rc.vocab_size)
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.9,
                        max_new_tokens=5, seed=11)

    def run(**kw):
        eng = ServeEngine(model, params, slots=2, max_len=16, ctx=CTX, **kw)
        rid = eng.submit({"tokens": p}, sp)
        return {o.request_id: o for o in eng.run_until_drained()}[rid]

    assert run().token_ids == run(paged=True, page_size=4).token_ids


# ---------------------------------------------------------------------------
# reclaim / leak behaviour
# ---------------------------------------------------------------------------

def test_no_leaked_pages_after_abort_and_eos():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=2, max_len=16, ctx=CTX,
                      paged=True, page_size=4)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 4), 0, rc.vocab_size)
    r1 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=8))
    ref = {o.request_id: o for o in eng.run_until_drained()}[r1]
    eos = ref.token_ids[2]                   # a token the stream emits

    r_eos = eng.submit({"tokens": p},
                       SamplingParams(max_new_tokens=8, eos_id=eos))
    r_abort = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=8))
    collected = eng.step()                   # admits both requests
    assert eng.allocator.pages_in_use > 0
    out = eng.abort(r_abort)
    assert out.finish_reason == "abort"
    outs = {o.request_id: o
            for o in collected + [out] + eng.run_until_drained()}
    assert outs[r_eos].finish_reason == "eos"
    assert eng.allocator.pages_in_use == 0
    eng.allocator.check()
    # the freed pages are immediately reusable for a fresh request
    r2 = eng.submit({"tokens": p}, SamplingParams(max_new_tokens=8))
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert outs[r2].token_ids == ref.token_ids


def test_admission_waits_for_pages_then_resumes():
    """A pool smaller than the burst forces queueing; freed pages admit
    the queue mid-flight (continuous batching) and nothing starves."""
    rc, model, params = _lm()
    # pool fits exactly one request's budget (4 prompt + 4 gen = 2 pages)
    eng = ServeEngine(model, params, slots=2, max_len=16, ctx=CTX,
                      paged=True, page_size=4, num_pages=2)
    p = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, rc.vocab_size)
    sp = SamplingParams(max_new_tokens=4)
    ids = [eng.submit({"tokens": p}, sp) for _ in range(3)]
    eng.step()
    # two free slots but pages for only one request: one admitted
    assert eng.num_active == 1 and eng.num_pending == 2
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert sorted(outs) == sorted(ids)
    assert len({tuple(outs[i].token_ids) for i in ids}) == 1
    assert eng.allocator.pages_in_use == 0


def test_oversized_request_rejected_not_wedged():
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=8, ctx=CTX,
                      paged=True, page_size=4)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 6), 0, rc.vocab_size)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit({"tokens": p}, SamplingParams(max_new_tokens=4))


def test_request_larger_than_pool_fails_fast():
    """A reservation that can NEVER fit the pool must raise at submit,
    not wedge the FIFO admission head forever."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                      paged=True, page_size=4, num_pages=2)
    p = jax.random.randint(jax.random.PRNGKey(0), (1, 4), 0, rc.vocab_size)
    with pytest.raises(ValueError, match="pages"):
        eng.submit({"tokens": p}, SamplingParams(max_new_tokens=8))


def test_paged_kernel_impl_tracks_gather_impl():
    """Ctx(paged_attn_impl='kernel') routes decode through the Pallas
    paged-attention kernel (write-then-attend); its logits track the
    gather path closely. The paths differ only in when the fresh token
    is quantized, so int8 tolerates more than bf16."""
    from repro.models.layers import Ctx as MCtx
    rc, model, params = _lm("qwen2.5-14b")     # no attention windows
    p = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0, rc.vocab_size)
    for kv, tol in (("bf16", 5e-2), ("int8", 0.3)):
        eng = ServeEngine(model, params, slots=2, max_len=16, kv_dtype=kv,
                          ctx=CTX, paged=True, page_size=4)
        eng.submit({"tokens": p}, SamplingParams(max_new_tokens=6))
        eng.step()
        eng.step()                             # a couple of cache tokens
        ctx_k = MCtx(compute_dtype=jnp.float32, paged_attn_impl="kernel")
        _, lg_g = model.decode_step(CTX, params, eng.cur, eng.cache)
        _, lg_k = model.decode_step(ctx_k, params, eng.cur, eng.cache)
        err = float(jnp.max(jnp.abs(lg_g[0] - lg_k[0])))
        assert err < tol, (kv, err)
        # and greedy argmax agrees on this step
        assert int(jnp.argmax(lg_g[0, -1])) == int(jnp.argmax(lg_k[0, -1]))


# ---------------------------------------------------------------------------
# mixed source lengths (cross-attention cache fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True])
def test_mixed_source_lengths_one_engine(paged):
    """Requests with different source lengths coexist; each stream equals
    its solo run (no cross-cache contamination from the padding)."""
    rc = reduce_config(REGISTRY["nllb600m"])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(paged=True, page_size=4) if paged else {}
    sp = SamplingParams(max_new_tokens=5)
    srcs = [jax.random.randint(jax.random.PRNGKey(i), (1, se), 0,
                               rc.vocab_size)
            for i, se in enumerate((rc.enc_len, rc.enc_len - 2,
                                    rc.enc_len - 3))]

    def req(src):
        return {"src_tokens": src, "tgt_in": jnp.full((1, 1), 8, jnp.int32)}

    solo = []
    for src in srcs:
        eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX, **kw)
        rid = eng.submit(req(src), sp)
        solo.append({o.request_id: o
                     for o in eng.run_until_drained()}[rid].token_ids)

    eng = ServeEngine(model, params, slots=3, max_len=16, ctx=CTX, **kw)
    ids = [eng.submit(req(src), sp) for src in srcs]
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert [outs[i].token_ids for i in ids] == solo


def test_source_longer_than_capacity_rejected():
    rc = reduce_config(REGISTRY["nllb600m"])
    model = build_model(rc)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX)
    src = jax.random.randint(jax.random.PRNGKey(0), (1, rc.enc_len + 1), 0,
                             rc.vocab_size)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit({"src_tokens": src,
                    "tgt_in": jnp.full((1, 1), 8, jnp.int32)},
                   SamplingParams(max_new_tokens=4))


# ---------------------------------------------------------------------------
# occupancy / batched admission
# ---------------------------------------------------------------------------

def test_paged_occupancy_at_least_dense():
    """Equal page pool, paged spread over 2x slots: occupancy >= dense."""
    rc, model, params = _lm()
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (1, 4), 0,
                                  rc.vocab_size) for i in range(8)]
    sp = SamplingParams(max_new_tokens=4)

    def occupancy(**kw):
        eng = ServeEngine(model, params, slots=kw.pop("slots"), max_len=16,
                          ctx=CTX, **kw)
        for p in prompts:
            eng.submit({"tokens": p}, sp)
        eng.run_until_drained()
        return eng.occupancy

    occ_d = occupancy(slots=4)
    occ_p = occupancy(slots=8, paged=True, page_size=4,
                      num_pages=4 * pages_needed(16, 4))
    assert occ_p >= occ_d - 1e-9


def test_group_admission_is_batched_and_bounded():
    """A same-shape burst admits as ONE batched multi-slot prefill (one
    jitted executable), not one compile per request."""
    rc, model, params = _lm()
    eng = ServeEngine(model, params, slots=4, max_len=16, ctx=CTX,
                      paged=True, page_size=4)
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (1, 4), 0,
                                  rc.vocab_size) for i in range(4)]
    for p in prompts:
        eng.submit({"tokens": p}, SamplingParams(max_new_tokens=3))
    assert eng.num_active == 0               # admission deferred to step()
    eng.step()
    assert eng.num_active == 4               # one burst, all admitted
    assert eng.prefill_compiles == 1         # a single (4, 4) prefill shape
    eng.run_until_drained()
    cache_size = getattr(eng._prefill_paged_fn, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 1


@pytest.mark.parametrize("arch", ["gemma3-1b", "nllb600m"])
def test_teacher_forced_logits_pick_the_engine_streams(arch):
    """Scored on its own greedy streams, a paged engine's teacher-forced
    logits (one burst, its own prefill and decode routes) choose those
    streams at every step, and the engine is left idle with no page
    held."""
    rc, model, params = _lm(arch)
    key = "src_tokens" if arch == "nllb600m" else "tokens"
    n_tok = rc.enc_len if arch == "nllb600m" else 4
    reqs = [{key: jax.random.randint(jax.random.PRNGKey(i), (1, n_tok), 0,
                                     rc.vocab_size)} for i in range(4)]
    if arch == "nllb600m":
        reqs = [dict(r, tgt_in=jnp.full((1, 1), 8, jnp.int32)) for r in reqs]
    eng = ServeEngine(model, params, slots=4, max_len=16, ctx=CTX,
                      paged=True, page_size=4, horizon=4)
    ids = [eng.submit(r, SamplingParams(max_new_tokens=6)) for r in reqs]
    outs = {o.request_id: o for o in eng.run_until_drained()}
    streams = [outs[i].token_ids for i in ids]
    logits = eng.teacher_forced_logits(reqs, streams)
    assert logits.shape[:2] == (4, 6) and logits.dtype == jnp.float32
    assert jnp.argmax(logits, axis=-1).tolist() == streams
    assert eng.allocator.pages_in_use == 0
    eng.allocator.check()
    eng.submit(reqs[0], SamplingParams(max_new_tokens=6))
    with pytest.raises(ValueError, match="idle"):
        eng.teacher_forced_logits(reqs[:1], streams[:1])
    dense = ServeEngine(model, params, slots=4, max_len=16, ctx=CTX)
    with pytest.raises(ValueError, match="paged"):
        dense.teacher_forced_logits(reqs[:1], streams[:1])


def test_group_admission_mixed_lengths_buckets():
    """Different prompt lengths in one burst: the group pads to the head
    request's bucket; distinct buckets admit as separate groups."""
    rc, model, params = _lm()
    sp = SamplingParams(max_new_tokens=3)

    def solo(p):
        eng = ServeEngine(model, params, slots=1, max_len=16, ctx=CTX,
                          paged=True, page_size=4)
        rid = eng.submit({"tokens": p}, sp)
        return {o.request_id: o for o in eng.run_until_drained()}[rid]

    prompts = [jax.random.randint(jax.random.PRNGKey(i), (1, n), 0,
                                  rc.vocab_size)
               for i, n in enumerate((3, 4, 6, 5))]
    refs = [solo(p).token_ids for p in prompts]
    eng = ServeEngine(model, params, slots=4, max_len=16, ctx=CTX,
                      paged=True, page_size=4)
    ids = [eng.submit({"tokens": p}, sp) for p in prompts]
    outs = {o.request_id: o for o in eng.run_until_drained()}
    assert [outs[i].token_ids for i in ids] == refs
