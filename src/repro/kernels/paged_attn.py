"""Flash-decode attention over a block-paged KV cache (int8 or bf16).

vLLM-style paged attention for the TPU serving engine: the KV cache
lives in a shared pool of fixed-size pages; each sequence owns a chain
of pages named by a per-sequence block table. The kernel walks the
table with the *grid index map* — the page id selects which block of
the pool is DMA'd into VMEM — so no gathered dense copy of the cache is
ever materialized in HBM. Block tables and valid lengths arrive via
scalar prefetch (available before the body runs, as required for
index-map use).

Layouts (prepared by kernels.ops.paged_decode_attention):
  q          (B, Hkv, G, d)    G = query heads per KV head, padded >= 8
  k_pages    (P, Hkv, ps, d)   int8 codes or bf16   [v_pages likewise]
  k_scales   (P, Hkv, ps) f32  absent on the bf16 path; viewed as
                               (P, Hkv, 1, ps) so a page's block
                               (1, 1, 1, ps) meets the TPU tiling rule
  block_tables (B, maxp) int32 page ids; out-of-chain entries must name
                               a reserved trash page (masked by length)
  lengths    (B,) int32        valid token count per sequence
Grid (B, Hkv, maxp), page dimension innermost ("arbitrary") so the
online-softmax accumulators carry across a sequence's chain.

This module is kept ruff-format-clean (CI lint job checks it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attn_call"]

_NEG_INF = -1e30


def _kernel(
    len_ref,
    tbl_ref,
    q_ref,
    k_ref,
    ks_ref,
    v_ref,
    vs_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    ps: int,
    sm_scale: float,
    quantized: bool,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (ps, d)
    v = v_ref[0, 0].astype(jnp.float32)
    scores = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * sm_scale
    )  # (G, ps)
    if quantized:
        # per-token scales arrive as a (1, ps) row: scale the score
        # columns (q . k_t * s_t) instead of the K rows
        scores = scores * ks_ref[0, 0]

    # page p of the chain holds token positions [p*ps, (p+1)*ps)
    pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = pos < len_ref[b]
    scores = jnp.where(valid, scores, _NEG_INF)

    m_old = m_ref[:, :1]  # (G, 1)
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    prob = jnp.exp(scores - m_new)
    prob = jnp.where(valid, prob, 0.0)

    l_new = l_ref[:, :1] * alpha + jnp.sum(prob, axis=-1, keepdims=True)
    # sum_t p_t * (v_t * s_t) == (p * s) @ v: the V scales weight the
    # probabilities, not the V rows
    pv = prob * vs_ref[0, 0] if quantized else prob
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == pl.num_programs(2) - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "out_dtype", "interpret"))
def paged_attn_call(
    q,
    k_pages,
    k_scales,
    v_pages,
    v_scales,
    block_tables,
    lengths,
    *,
    sm_scale: float,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    """q (B,Hkv,G,d) against paged K/V; scales may be None (bf16 path)."""
    B, Hkv, G, d = q.shape
    ps = k_pages.shape[2]
    maxp = block_tables.shape[1]
    quantized = k_scales is not None

    # the page id comes from the prefetched block table: block index maps
    # receive the scalar-prefetch refs after the grid indices
    def kv_map(b, h, p, lens, tbl):
        return (tbl[b, p], h, 0, 0)

    def sc_map(b, h, p, lens, tbl):
        return (tbl[b, p], h, 0, 0)

    def q_map(b, h, p, lens, tbl):
        return (b, h, 0, 0)

    kv_spec = pl.BlockSpec((1, 1, ps, d), kv_map)
    sc_spec = pl.BlockSpec((1, 1, 1, ps), sc_map)
    q_spec = pl.BlockSpec((1, 1, G, d), q_map)

    if quantized:
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec]
        args = [q, k_pages, k_scales[:, :, None], v_pages, v_scales[:, :, None]]
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        args = [q, k_pages, v_pages]

    def kernel(len_ref, tbl_ref, *refs):
        if quantized:
            q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, acc, m_sc, l_sc = refs
        else:
            q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc = refs
            ks_ref = vs_ref = None
        _kernel(
            len_ref,
            tbl_ref,
            q_ref,
            k_ref,
            ks_ref,
            v_ref,
            vs_ref,
            o_ref,
            acc,
            m_sc,
            l_sc,
            ps=ps,
            sm_scale=sm_scale,
            quantized=quantized,
        )

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),  # acc
            pltpu.VMEM((G, 128), jnp.float32),  # running max (col-bcast)
            pltpu.VMEM((G, 128), jnp.float32),  # running denom
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_decode_attn",
    )(lengths, block_tables, *args)
