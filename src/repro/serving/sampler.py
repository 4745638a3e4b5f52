"""Fused per-slot token sampler: one executable for every SamplingParams.

The engine decodes all slots in one batched step; slots may carry
different SamplingParams (greedy next to nucleus-sampled). To keep a
single compiled function regardless of the mix, the per-slot knobs
(temperature / top_k / top_p / PRNG key / stream offset) enter as traced
arrays and the greedy-vs-sampled choice is a data-dependent `where` —
changing a request's params never recompiles, only re-runs.

Per-slot PRNG streams: each request owns a base key derived from its
``seed``; token ``t`` of that request draws from ``fold_in(key, t)``, so
outputs are reproducible independent of slot placement, admission order,
or what the other slots are doing.

Both entry points are pure jnp, so they compose with ``jax.lax.scan``:
``sample_tokens`` is the per-token form the engine's legacy step uses,
``sample_tokens_scan`` is the horizon-fused scan-body form — identical
sampling, plus an ``alive`` mask so slots retired mid-horizon (EOS /
budget) emit ``pad_id`` instead of a live draw. The PRNG stream is
offset-indexed either way, so fused and per-token decode produce the
same tokens for the same request.

Poisoned-request isolation: a slot whose logits contain NaN/Inf (an
overflowed sub-octet arm, a numerically fragile quant format) samples
the ``ERR_TOKEN`` sentinel instead of garbage. The guard is per-row —
the other slots in the fused batch sample normally — and the engine
retires the offending slot with ``finish_reason='error'`` when the
sentinel reaches the host walk, so one poisoned request never takes
down a batch or escapes ``step()`` as an exception.

Both entry points trace under ``jax.named_scope("sampler")``, so a
profile lays the sampler's device operations (the vocabulary sorts
among them) to that name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sample_tokens", "sample_tokens_scan", "ERR_TOKEN"]

_NEG = jnp.float32(-1e30)   # mask value: exp() underflows to exactly 0

# sentinel "token" emitted for a slot whose logits are non-finite; never a
# valid vocab id, never equal to a pad (0) or any eos_id, so the host walk
# can detect it unambiguously in a synced block
ERR_TOKEN = -2


def _sample_row(logits, temp, top_k, top_p, key, offset):
    """One slot's next token. logits (V,) f32; scalars are traced."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)

    lg = logits / jnp.maximum(temp, 1e-6)
    # top-k: keep logits >= the k-th largest (k <= 0 disables)
    kk = jnp.where(top_k <= 0, v, jnp.minimum(top_k, v))
    srt = jnp.sort(lg)[::-1]
    kth = srt[jnp.maximum(kk - 1, 0)]
    lg = jnp.where(lg < kth, _NEG, lg)
    # top-p (nucleus): keep the smallest prefix of the sorted probability
    # mass reaching p; the top-1 token is always kept
    probs = jax.nn.softmax(lg)
    sp = jnp.sort(probs)[::-1]
    keep = (jnp.cumsum(sp) - sp) < top_p
    pth = jnp.min(jnp.where(keep, sp, jnp.inf))
    lg = jnp.where(probs < pth, _NEG, lg)

    tok = jax.random.categorical(jax.random.fold_in(key, offset), lg)
    return jnp.where(temp <= 0.0, greedy, tok).astype(jnp.int32)


def sample_tokens(logits, temps, top_ks, top_ps, keys, offsets):
    """Batched next-token sampling across slots.

    logits (S, V) f32, temps/top_ps (S,) f32, top_ks/offsets (S,) i32,
    keys (S, 2) u32 -> tokens (S,) i32. Rows with any non-finite logit
    return ``ERR_TOKEN`` (see module docstring) instead of a draw.
    """
    with jax.named_scope("sampler"):
        lg = logits.astype(jnp.float32)
        toks = jax.vmap(_sample_row)(lg, temps, top_ks, top_ps, keys,
                                     offsets)
        ok = jnp.all(jnp.isfinite(lg), axis=-1)
        return jnp.where(ok, toks, jnp.int32(ERR_TOKEN))


def sample_tokens_scan(logits, temps, top_ks, top_ps, keys, offsets, alive,
                       pad_id: int = 0):
    """Scan-body form of ``sample_tokens`` for horizon-fused decode.

    Same sampling semantics (including the non-finite-logits ERR_TOKEN
    guard), plus an ``alive`` (S,) i32 mask: slots that retired earlier
    in the horizon (EOS or exhausted ``max_new_tokens`` budget) emit
    ``pad_id`` — the host-side walk of the emitted token block stops at
    each slot's retirement point, so pads are never read as generated
    tokens (a dead slot's poisoned logits are masked, not flagged).
    """
    toks = sample_tokens(logits, temps, top_ks, top_ps, keys, offsets)
    with jax.named_scope("sampler"):
        return jnp.where(alive > 0, toks, jnp.int32(pad_id))
