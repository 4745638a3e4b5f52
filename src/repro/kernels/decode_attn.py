"""Flash-decode attention over a *quantized* (int8) KV cache.

Beyond-paper kernel: the paper quantizes weights; decode on TPU is bound
by KV-cache HBM reads, so we extend the same blockwise-absmax scheme to
the KV cache and dequantize per tile in VMEM (same move as qmm.py, applied
to activations-at-rest). Online-softmax accumulation over the sequence
grid dimension; per-sequence valid lengths arrive via scalar prefetch so
one compiled kernel serves ragged continuous batches.

Layouts (prepared by kernels.ops.decode_attention):
  q        (B, Hkv, G, d)   G = query heads per KV head, padded to >=8
  k_codes  (B, Hkv, S, d)   int8        k_scales (B, Hkv, S) f32
  v_codes  (B, Hkv, S, d)   int8        v_scales (B, Hkv, S) f32
  (scales are viewed as (B, Hkv, 1, S) so their (1, 1, 1, bs) block
  meets the TPU tiling rule)
  lengths  (B,) int32       valid KV length per sequence
Grid (B, Hkv, S/bs), sequence innermost ("arbitrary").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attn_call"]

_NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
            acc_ref, m_ref, l_ref, *, bs: int, sm_scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bs, d)
    # per-token scales are a (1, bs) row: they scale score columns
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale * ks_ref[0, 0]

    pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = pos < len_ref[b]
    scores = jnp.where(valid, scores, _NEG_INF)

    m_old = m_ref[:, :1]                                    # (G, 1)
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)                          # (G, 1)
    p = jnp.exp(scores - m_new)                             # (G, bs)
    p = jnp.where(valid, p, 0.0)

    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                     # (bs, d)
    # (p * s) @ v == p @ (v * s[:, None])
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p * vs_ref[0, 0], v, preferred_element_type=jnp.float32)

    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "sm_scale", "out_dtype",
                                             "interpret"))
def decode_attn_call(q, k_codes, k_scales, v_codes, v_scales, lengths, *,
                     bs: int, sm_scale: float, out_dtype=jnp.bfloat16,
                     interpret: bool = False):
    B, Hkv, G, d = q.shape
    S = k_codes.shape[2]
    assert S % bs == 0, (S, bs)

    grid = (B, Hkv, S // bs)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, s, L: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b, h, s, L: (b, h, s, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b, h, s, L: (b, h, 0, s)),
            pl.BlockSpec((1, 1, bs, d), lambda b, h, s, L: (b, h, s, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b, h, s, L: (b, h, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, h, s, L: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),     # acc
            pltpu.VMEM((G, 128), jnp.float32),   # running max (col-bcast)
            pltpu.VMEM((G, 128), jnp.float32),   # running denom
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, sm_scale=sm_scale),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn_int8kv",
    )(lengths, q, k_codes, k_scales[:, :, None], v_codes, v_scales[:, :, None])
