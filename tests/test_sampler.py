"""The sampler's batch-level greedy gate.

``sample_tokens`` / ``sample_tokens_scan`` run the full sampling path
(two whole-vocabulary sorts per row) only when a live row samples; a
greedy batch takes a row-wise argmax. The gate must change cost, never
tokens: every case below is compared, token for token, with an inline
copy of the ungated path (the vmapped per-row sampler, then the
non-finite guard, then the alive mask). The jaxpr test pins the
structure, and the engine test the ``sampler_full_steps`` counter that
reports how often the full path was dispatched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduce_config
from repro.models import Ctx, build_model
from repro.serving import ERR_TOKEN, SamplingParams, ServeEngine
from repro.serving.sampler import sample_tokens, sample_tokens_scan

S, V, PAD = 6, 1000, 0
_NEG = jnp.float32(-1e30)


# ---------------------------------------------------------------------------
# the ungated path, copied inline
# ---------------------------------------------------------------------------

def _ref_row(logits, temp, top_k, top_p, key, offset):
    v = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)
    lg = logits / jnp.maximum(temp, 1e-6)
    kk = jnp.where(top_k <= 0, v, jnp.minimum(top_k, v))
    srt = jnp.sort(lg)[::-1]
    kth = srt[jnp.maximum(kk - 1, 0)]
    lg = jnp.where(lg < kth, _NEG, lg)
    probs = jax.nn.softmax(lg)
    sp = jnp.sort(probs)[::-1]
    keep = (jnp.cumsum(sp) - sp) < top_p
    pth = jnp.min(jnp.where(keep, sp, jnp.inf))
    lg = jnp.where(probs < pth, _NEG, lg)
    tok = jax.random.categorical(jax.random.fold_in(key, offset), lg)
    return jnp.where(temp <= 0.0, greedy, tok).astype(jnp.int32)


@jax.jit
def _ref(logits, temps, top_ks, top_ps, keys, offsets):
    lg = logits.astype(jnp.float32)
    toks = jax.vmap(_ref_row)(lg, temps, top_ks, top_ps, keys, offsets)
    ok = jnp.all(jnp.isfinite(lg), axis=-1)
    return jnp.where(ok, toks, jnp.int32(ERR_TOKEN))


def _ref_scan(logits, temps, top_ks, top_ps, keys, offsets, alive):
    toks = _ref(logits, temps, top_ks, top_ps, keys, offsets)
    return jnp.where(alive > 0, toks, jnp.int32(PAD))


# ---------------------------------------------------------------------------
# the batches
# ---------------------------------------------------------------------------

def _batch(case, seed):
    """(args of sample_tokens, alive) for one named case."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = jax.random.normal(k[0], (S, V), jnp.float32) * 3.0
    temps = np.zeros((S,), np.float32)
    top_ks = np.zeros((S,), np.int32)
    top_ps = np.ones((S,), np.float32)
    alive = np.ones((S,), np.int32)
    if case == "greedy_nan":
        logits = logits.at[2, 17].set(jnp.nan)
    elif case == "greedy_dead_sampled":
        # a retired slot keeps its last request's temperature
        temps[4] = 0.9
        top_ks[4] = 5
        alive[4] = 0
        logits = logits.at[4, 3].set(jnp.inf)
    elif case == "mixed":
        temps[[1, 3]] = [0.7, 1.3]
        top_ks[1] = 20
        top_ps[3] = 0.8
    elif case == "sampled":
        temps[:] = np.linspace(0.5, 1.5, S)
        top_ks[:] = [0, 1, 8, 50, 0, 300]
        top_ps[:] = [1.0, 0.9, 0.5, 0.95, 0.3, 0.99]
    keys = jax.vmap(jax.random.PRNGKey)(
        jax.random.randint(k[1], (S,), 0, 2 ** 30))
    offsets = jax.random.randint(k[2], (S,), 0, 64)
    args = (logits, jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(top_ps), keys, offsets)
    return args, jnp.asarray(alive)


CASES = ("greedy", "greedy_nan", "greedy_dead_sampled", "mixed", "sampled")


@pytest.mark.parametrize("seed", (0, 1, 2))
@pytest.mark.parametrize("case", CASES)
def test_gated_sampler_matches_ungated_path(case, seed):
    args, alive = _batch(case, seed)
    want = np.asarray(_ref(*args))
    got = np.asarray(jax.jit(sample_tokens)(*args))
    np.testing.assert_array_equal(got, want)
    got_scan = np.asarray(jax.jit(sample_tokens_scan)(*args, alive))
    np.testing.assert_array_equal(got_scan,
                                  np.asarray(_ref_scan(*args, alive)))
    if case == "greedy_nan":
        assert got[2] == ERR_TOKEN and got_scan[2] == ERR_TOKEN
    if case == "greedy_dead_sampled":
        assert got_scan[4] == PAD
    if case.startswith("greedy"):
        live = np.isfinite(np.asarray(args[0])).all(-1)
        np.testing.assert_array_equal(
            got[live], np.asarray(jnp.argmax(args[0], -1))[live])


@pytest.mark.parametrize("case,full", (
    ("greedy", False), ("greedy_nan", False), ("greedy_dead_sampled", False),
    ("mixed", True), ("sampled", True)))
def test_full_path_runs_only_when_a_live_row_samples(case, full,
                                                     monkeypatch):
    """Which branch ran, seen through a per-row sampler that marks its
    rows: a dead row's leftover temperature must not turn it on."""
    import repro.serving.sampler as sampler_mod

    mark = -7
    monkeypatch.setattr(sampler_mod, "_sample_row",
                        lambda *a: jnp.int32(mark))
    args, alive = _batch(case, 0)
    got = np.asarray(sample_tokens_scan(*args, alive))
    finite = np.isfinite(np.asarray(args[0])).all(-1)
    rows = (np.asarray(alive) > 0) & finite
    assert (got[rows] == mark).all() if full else (got != mark).all()


# ---------------------------------------------------------------------------
# structure: the greedy branch holds no sort
# ---------------------------------------------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _prims(jaxpr):
    return {e.primitive.name for e in _eqns(jaxpr)}


@pytest.mark.parametrize("scan", (False, True), ids=("tokens", "scan"))
def test_greedy_branch_has_no_sort(scan):
    args, alive = _batch("mixed", 0)
    fn = sample_tokens_scan if scan else sample_tokens
    jaxpr = jax.make_jaxpr(fn)(*args, *((alive,) if scan else ()))
    conds = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    greedy, full = conds[0].params["branches"]   # predicate false, true
    assert "sort" not in _prims(greedy.jaxpr)
    assert "argmax" in _prims(greedy.jaxpr)
    assert "sort" in _prims(full.jaxpr)
    # the sorts live nowhere but the sampled branch

    def sorts(j):
        return sum(e.primitive.name == "sort" for e in _eqns(j))

    assert sorts(jaxpr.jaxpr) == sorts(full.jaxpr) > 0


# ---------------------------------------------------------------------------
# the engine's sampler_full_steps counter
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    rc = reduce_config(REGISTRY["gemma3-1b"])
    model = build_model(rc)
    return rc, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("horizon", (1, 4))
def test_sampler_full_steps_counts_sampled_dispatches(lm, horizon):
    rc, model, params = lm
    eng = ServeEngine(model, params, slots=2, max_len=24,
                      ctx=Ctx(compute_dtype=jnp.float32), horizon=horizon)
    prompts = [jax.random.randint(jax.random.PRNGKey(i + 1), (1, 4 + 2 * i),
                                  0, rc.vocab_size) for i in range(2)]

    def serve(sp):
        for p, n in zip(prompts, (5, 9)):
            eng.submit({"tokens": p}, SamplingParams(max_new_tokens=n,
                                                     **sp))
        eng.run_until_drained()
        return eng.metrics()

    m = serve({})
    assert m.decode_steps > 0 and m.sampler_full_steps == 0
    eng.reset_metrics()
    m = serve({"temperature": 0.8, "top_p": 0.9, "seed": 3})
    assert m.decode_steps > 0
    assert m.sampler_full_steps == m.decode_steps
    assert f"repro_serving_sampler_full_steps {m.decode_steps}" in \
        eng.prometheus()
    eng.reset_metrics()
    assert eng.metrics().sampler_full_steps == 0
    assert "repro_serving_sampler_full_steps 0" in eng.prometheus()
