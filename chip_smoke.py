"""Bring-up check on one TPU: serve nllb600m at its published widths.

Drives the serving path a user calls (``deploy`` -> ``ServeEngine`` ->
``submit`` / ``stream``) with random weights from ``--seed`` and
synthetic sources from ``SyntheticTranslation(seed=...)``, in one
process, and checks what comes out. Phases, one output line each:

  env      platform, device kind and count, jax/jaxlib/libtpu versions,
           the compile-cache directory
  kernels  the Pallas paged-attention kernel (bf16 and int8 pages) and
           qmm (int4 and fp4) at nllb600m widths, against the jnp
           oracles in repro.kernels.ref
  serve    each weight format: 8 requests with full-length (enc_len)
           sources through a paged engine; every request must finish
           on its length budget with no slot error
  parity   int4: a dense-cache engine gives the paged engine's streams
           when both prefill each request alone
  pallas   int4 through Pallas qmm + the paged kernel: the kernels are
           in the decode executable, and under teacher forcing on the
           default route's streams its logits agree with the default
           route's within LOGIT_RTOL at every step
  cache    compiles that consulted the persistent cache, and its hits

``--four-chips`` runs only the four-chip phase instead: the int4 paged
engine on one device is the reference for a tensor-parallel engine
over all four devices (tp4) and for four one-device replicas behind a
router (dp4: each replica against the reference serving the same
requests in the same admission group), logits within LOGIT_RTOL at
every step.

Run from the root of a checkout:  python3 chip_smoke.py [--four-chips]

A failed check raises and exits non-zero. So does a run that finds no
TPU, or in which Pallas kernels would be interpreted. Only a passing
run prints its last line: one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "nllb600m"
POLICIES = ("bf16", "int8", "int4", "fp8", "fp4")
SLOTS = 8
REQUESTS = 8
MAX_LEN = 64
GEN = 32
HORIZON = 16
PAGE_SIZE = 8

# Kernel tolerances, against oracles computed in f32 at "highest"
# matmul precision. Attention outputs are convex combinations of
# N(0, 1) values, so O(1): 3e-2 admits bf16 rounding of the kernel's
# MXU passes and nothing structural (a wrong page or a dropped scale
# moves outputs by O(1)). qmm feeds the MXU bf16 activations and bf16
# dequantized weights with f32 accumulation: its error relative to the
# largest output stays near 2**-8.
ATTN_ATOL = 3e-2
QMM_RTOL = 1e-2
# Pallas route and tp4 against the one-device default route, int4: the
# largest |logit difference| under teacher forcing on the reference
# stream, relative to the reference's largest |logit|, at every step.
# The routes round differently: qmm rounds the dequantized tile to bf16
# and accumulates per K tile where XLA fuses dequant and dot; the paged
# kernel attends the new token's K/V after int8 quantization
# (write-then-attend) where the gather route attends it unquantized;
# tp4 sums partial products across chips. Greedy decoding of random
# weights over a 256k vocabulary has near ties, so such rounding may
# flip a token and part the streams; the logits must still agree.
# Measured on a v5e before this limit was set: first-step differences
# of 1.01e-2 (Pallas), 1.04e-2 (tp4) and 1.08e-2 (a prefill batch of 8
# against 1). The limit is about twice the largest, for the later steps.
LOGIT_RTOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of this script failed."""


def require(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def report(phase: str, t0: float, msg: str) -> None:
    print(f"[{phase}] {msg} ({time.perf_counter() - t0:.1f} s)", flush=True)


# ---------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------

def env_phase(cache_dir: str) -> None:
    import importlib.metadata as md

    import jax
    import jaxlib

    t0 = time.perf_counter()
    require(not os.environ.get("REPRO_PALLAS_INTERPRET"),
            "REPRO_PALLAS_INTERPRET is set: kernels would be interpreted")
    require(jax.default_backend() == "tpu",
            f"JAX backend is {jax.default_backend()!r}, not 'tpu'")
    from repro.kernels.ops import interpret_mode
    require(not interpret_mode(), "Pallas kernels would run interpreted")
    d = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    report("env", t0, f"platform {d.platform}, kind {d.device_kind}, "
           f"{len(jax.devices())} devices, jax {jax.__version__}, jaxlib "
           f"{jaxlib.__version__}, libtpu {libtpu}, compile cache "
           f"{cache_dir}")


def kernels_phase(cfg, seed: int) -> None:
    """Pallas kernels at the model's widths against their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import QTensor
    from repro.kernels import ops, ref

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    H, Hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    maxp = MAX_LEN // PAGE_SIZE
    P = 1 + SLOTS * maxp                       # page 0 is the trash page
    q = jnp.asarray(rng.standard_normal((SLOTS, H, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((P, PAGE_SIZE, Hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, PAGE_SIZE, Hkv, d)), jnp.float32)
    tables = jnp.asarray((1 + rng.permutation(P - 1)).reshape(SLOTS, maxp),
                         jnp.int32)
    lens = jnp.asarray(rng.integers(1, MAX_LEN + 1, SLOTS), jnp.int32)
    G = H // Hkv
    kv_t = (0, 2, 1, 3)                        # pool -> kernel layout
    lines = []
    for pages in ("bf16", "int8"):
        if pages == "bf16":
            kp, vp = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
            out = ops.paged_decode_attention(q, kp, vp, tables, lens,
                                             out_dtype=jnp.float32)
            oracle_args = (jnp.transpose(kp.astype(jnp.float32), kv_t), None,
                           jnp.transpose(vp.astype(jnp.float32), kv_t), None)
        else:
            kc, ks = ops.quantize_kv(k)
            vc, vs = ops.quantize_kv(v)
            out = ops.paged_decode_attention(q, kc, vc, tables, lens,
                                             k_scales=ks, v_scales=vs,
                                             out_dtype=jnp.float32)
            oracle_args = (jnp.transpose(kc, kv_t), jnp.transpose(ks, (0, 2, 1)),
                           jnp.transpose(vc, kv_t), jnp.transpose(vs, (0, 2, 1)))
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attn_ref(q.reshape(SLOTS, Hkv, G, d),
                                      *oracle_args, tables, lens, d ** -0.5)
        err = float(jnp.max(jnp.abs(out - want.reshape(SLOTS, H, d))))
        require(err <= ATTN_ATOL, f"paged attention, {pages} pages: max abs "
                f"error {err:.3g} > {ATTN_ATOL}")
        lines.append(f"paged_attn {pages} {err:.2e}")
    for fmt in ("int4", "fp4"):
        for K, N in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
            w = jnp.asarray(rng.standard_normal((K, N)) * K ** -0.5,
                            jnp.float32)
            x = jnp.asarray(rng.standard_normal((SLOTS, K)), jnp.bfloat16)
            qt = QTensor.quantize(w, fmt, block_size=64)
            y = ops.qmm(x, qt, compute_dtype=jnp.bfloat16)
            with jax.default_matmul_precision("highest"):
                want = ref.qmm_ref(x, qt.data, qt.block_scales(), fmt)
            err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - want)))
            scale = float(jnp.max(jnp.abs(want)))
            require(err <= QMM_RTOL * scale, f"qmm {fmt} {K}x{N}: max abs "
                    f"error {err:.3g} > {QMM_RTOL} x {scale:.3g}")
            lines.append(f"qmm {fmt} {SLOTS}x{K}x{N} {err:.2e}/{scale:.2f}")
    report("kernels", t0, "max abs error: " + ", ".join(lines)
           + f" (tolerance attn {ATTN_ATOL}, qmm {QMM_RTOL} x max|ref|)")


def make_requests(cfg, seed: int) -> list:
    """REQUESTS enc-dec requests: full-length sources, lang-code prompt."""
    import jax.numpy as jnp

    from repro.data import SyntheticTranslation

    b = SyntheticTranslation(cfg.vocab_size, cfg.enc_len, seed=seed).sample(
        REQUESTS)
    return [{"src_tokens": jnp.asarray(b["src_tokens"][i:i + 1]),
             "tgt_in": jnp.asarray(b["tgt_in"][i:i + 1, :1])}
            for i in range(REQUESTS)]


def submit(pipe, requests) -> list:
    """Submit every request, greedy with a GEN-token budget; returns
    their ids."""
    from repro.serving import SamplingParams

    sp = SamplingParams(max_new_tokens=GEN)
    return [pipe.engine.submit(r, sp) for r in requests]


def serve(pipe, requests, rids=None) -> list:
    """Submit every request (unless ``rids`` says they were), drain
    through ``engine.stream()``, check each finishes on its budget;
    returns the token streams in order."""
    engine = pipe.engine
    if rids is None:
        rids = submit(pipe, requests)
    outs = {o.request_id: o for o in engine.stream()}
    require(sorted(outs) == sorted(rids),
            f"drained {sorted(outs)}, submitted {sorted(rids)}")
    for rid in rids:
        o = outs[rid]
        require(o.finish_reason == "length" and len(o.token_ids) == GEN,
                f"request {rid}: {o.finish_reason} after "
                f"{len(o.token_ids)} tokens, expected length after {GEN}")
    m = engine.metrics()
    require(m.slot_errors == 0, f"{m.slot_errors} slot errors")
    return [list(outs[rid].token_ids) for rid in rids]


def _deploy(cfg, policy, raw, **kw):
    from repro.serving import deploy

    return deploy(cfg, policy, params=raw, slots=SLOTS, max_len=MAX_LEN,
                  paged=kw.pop("paged", True), page_size=PAGE_SIZE,
                  horizon=HORIZON, **kw)


def serve_phase(cfg, raw, requests, policies=POLICIES):
    """Serve the requests once per weight format; returns the streams
    and the int4 pipeline (the default route the other phases compare
    with)."""
    import jax

    streams, int4 = {}, None
    for policy in policies:
        t0 = time.perf_counter()
        pipe = _deploy(cfg, policy, raw)
        streams[policy] = serve(pipe, requests)
        stats = jax.devices()[0].memory_stats() or {}
        peak, now = stats.get("peak_bytes_in_use"), stats.get("bytes_in_use")
        report(f"serve {policy}", t0,
               f"{pipe.spec_str}: {REQUESTS} requests x {GEN} tokens, all "
               f"'length', 0 slot errors; model {pipe.quantized_bytes} bytes "
               f"(f32 {pipe.fp_bytes}); device peak_bytes_in_use {peak}, "
               f"bytes_in_use {now}")
        if policy == "int4":
            int4 = pipe
        del pipe
    return streams, int4


def parity_phase(cfg, raw, requests, paged_pipe) -> None:
    """int4 dense-cache engine against the paged one at equal grouping.
    The dense engine prefills each request alone. Fed one request at a
    time, the paged engine prefills alone too, and its streams must be
    identical: paging changes no number. (A burst prefills as one
    batch, whose bf16 rounding on the chip differs from a batch of 1's:
    that is grouping, not paging; ROADMAP 3.9.)"""
    t0 = time.perf_counter()
    dense = _deploy(cfg, "int4", raw, paged=False)
    dense_streams = serve(dense, requests)
    alone = [serve(paged_pipe, [r])[0] for r in requests]
    same = sum(a == d for a, d in zip(alone, dense_streams))
    require(same == REQUESTS, f"int4 paged engine fed one request at a "
            f"time: {same}/{REQUESTS} streams equal the dense engine's")
    report("parity", t0, f"int4 dense vs paged, each request prefilled "
           f"alone: {same}/{REQUESTS} streams identical")


def compare_routes(name, engine, got, ref_engine, want, requests) -> str:
    """``engine``'s streams ``got`` against the reference engine's
    ``want`` on the same requests, admitted as one burst by both. Both
    engines score ``want`` under teacher forcing; at every step of
    every request their logits must agree within LOGIT_RTOL of the
    reference's largest |logit|, whether or not the streams part."""
    import jax.numpy as jnp
    import numpy as np

    n = len(want)
    same = sum(g == w for g, w in zip(got, want))
    parts = {i: next(t for t, (a, b) in enumerate(zip(g, w)) if a != b)
             for i, (g, w) in enumerate(zip(got, want)) if g != w}
    x = ref_engine.teacher_forced_logits(requests, want)
    y = engine.teacher_forced_logits(requests, want)
    rel = np.asarray(jnp.max(jnp.abs(x - y), axis=-1)
                     / jnp.max(jnp.abs(x), axis=-1))          # (n, GEN)
    own = int(jnp.sum(jnp.argmax(x, axis=-1) == jnp.asarray(want)))
    i, t = np.unravel_index(int(np.argmax(rel)), rel.shape)
    worst = float(rel[i, t])
    require(worst <= LOGIT_RTOL,
            f"{name}: teacher-forced logits differ by {worst:.3g} x "
            f"max|logit| at request {i}, step {t} > {LOGIT_RTOL}")
    streams = f"{same}/{n} streams identical"
    if parts:
        streams += (f", the others part at tokens {list(parts.values())} "
                    f"where the logits differ by " + ", ".join(
                        f"{rel[i_, t_]:.2e}" for i_, t_ in parts.items()))
    return (f"{streams}; teacher-forced logits agree within {worst:.2e} x "
            f"max|logit| over all {n}x{GEN} steps (tolerance {LOGIT_RTOL}; "
            f"worst at request {i}, step {t}; first step "
            f"{rel[:, 0].max():.2e}); the reference's scored logits pick "
            f"its own stream at {own}/{rel.size} steps")


_CUSTOM_CALL = re.compile(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                          r'custom_call_target="tpu_custom_call"')


def decode_kernels(pipe) -> dict:
    """Pallas kernels (by name) in the compiled decode step of ``pipe``:
    the body of the engine's fused horizon scan, with its Ctx."""
    import collections

    import jax
    import jax.numpy as jnp

    engine = pipe.engine
    tok = jnp.zeros((engine.n_slots, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: pipe.model.decode_step(pipe.ctx, p, t, c)
    ).lower(engine.params, tok, engine.cache).compile().as_text()
    return dict(collections.Counter(_CUSTOM_CALL.findall(hlo)))


def pallas_phase(cfg, raw, requests, default_streams, default_pipe) -> None:
    t0 = time.perf_counter()
    pipe = _deploy(cfg, "int4", raw, matmul_impl="pallas",
                   paged_attn_impl="kernel")
    got = serve(pipe, requests)
    kernels = decode_kernels(pipe)
    require(any(n.startswith("qmm_") for n in kernels),
            f"no qmm kernel in the Pallas-route decode step: {kernels}")
    require("paged_decode_attn" in kernels,
            f"no paged attention kernel in the decode step: {kernels}")
    verdict = compare_routes("pallas route", pipe.engine, got,
                             default_pipe.engine, default_streams, requests)
    report("pallas", t0, f"int4 qmm + paged kernel: decode step holds "
           f"tpu_custom_calls {kernels}; vs default route: {verdict}")


def four_chips_phase(cfg, raw, requests) -> None:
    """tp4 and dp4 serving against a one-device reference engine."""
    import jax

    from repro.cluster import deploy_replicas, tp_mesh

    devs = jax.devices()
    require(len(devs) == 4, f"--four-chips needs 4 devices, have {len(devs)}")

    def placed(params):
        return set().union(*(leaf.sharding.device_set
                             for leaf in jax.tree.leaves(params)))

    t0 = time.perf_counter()
    ref = _deploy(cfg, "int4", raw)
    require(placed(ref.engine.params) == {devs[0]},
            f"reference params on {placed(ref.engine.params)}, not {devs[0]}")
    want = serve(ref, requests)
    report("four-chips reference", t0, f"int4 paged on {devs[0]}")

    t0 = time.perf_counter()
    tp = _deploy(cfg, "int4", raw, mesh=tp_mesh(4))
    require(placed(tp.engine.params) == set(devs),
            f"tp4 params on {placed(tp.engine.params)}, not all 4 devices")
    verdict = compare_routes("tp4", tp.engine, serve(tp, requests),
                             ref.engine, want, requests)
    report("four-chips tp4", t0, f"params over {len(devs)} devices; "
           f"vs reference: {verdict}")
    del tp

    t0 = time.perf_counter()
    dp = deploy_replicas(cfg, "int4", replicas=4, tp=1, params=raw,
                         slots=SLOTS, max_len=MAX_LEN, paged=True,
                         page_size=PAGE_SIZE, horizon=HORIZON)
    router = dp.engine
    for i, eng in enumerate(router.replicas):
        require(placed(eng.params) == {devs[i]},
                f"replica {i} params on {placed(eng.params)}, not {devs[i]}")
    rids = submit(dp, requests)
    groups = [[j for j, rid in enumerate(rids) if router.replica_of(rid) == i]
              for i in range(len(devs))]
    require(all(groups), f"the router left a replica idle: {groups}")
    got = serve(dp, requests, rids)
    # each replica admits its share as one burst; the reference serves
    # the same requests in the same burst, and both engines score its
    # streams at every step (on the chip, replica 0 on the reference's
    # device parted from it at equal grouping: streams may differ)
    verdicts = []
    for i, group in enumerate(groups):
        reqs = [requests[j] for j in group]
        verdicts.append(f"replica {i} {group}: " + compare_routes(
            f"dp4 replica {i}", router.replicas[i], [got[j] for j in group],
            ref.engine, serve(ref, reqs), reqs))
    burst = sum(g == w for g, w in zip(got, want))
    report("four-chips dp4", t0, f"replica i's params on device i only; vs "
           f"the reference serving each replica's burst: "
           + "; ".join(verdicts)
           + f" (vs its one burst of {REQUESTS}: {burst}/{REQUESTS} "
           f"streams identical)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (tp4, dp4)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the sources")
    args = ap.parse_args()
    t_start = time.perf_counter()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke.py: no src/repro next to {__file__}; run it "
                 "from the root of a checkout")
    sys.path.insert(0, src)
    from repro.runtime import CompileCacheCounter, configure_compile_cache

    cache_dir = configure_compile_cache()
    counter = CompileCacheCounter()
    env_phase(cache_dir)

    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    raw = build_model(cfg).init(jax.random.PRNGKey(args.seed))
    requests = make_requests(cfg, args.seed)
    report("setup", t0, f"{ARCH} random f32 weights (seed {args.seed}), "
           f"{REQUESTS} sources of {cfg.enc_len} tokens")

    if args.four_chips:
        four_chips_phase(cfg, raw, requests)
    else:
        kernels_phase(cfg, args.seed)
        streams, int4 = serve_phase(cfg, raw, requests)
        parity_phase(cfg, raw, requests, int4)
        pallas_phase(cfg, raw, requests, streams["int4"], int4)

    c = counter.counts()
    print(f"[cache] {cache_dir}: {c['requests']} compiles consulted it, "
          f"{c['hits']} hits, {c['writes']} written; "
          f"{'warm' if c['hits'] else 'cold'}", flush=True)
    print(f"[total] {time.perf_counter() - t_start:.1f} s", flush=True)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
