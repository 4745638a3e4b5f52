"""Fused dequant-matmul Pallas TPU kernel — the RMMEC SIMD MAC analogue.

Paper (Figs. 5-6): a SIMD MAC issues 6xINT4 / 6xFP4 / 3xFP8 / 1xBF16
multiplies per cycle into an output-stationary systolic array with a wide
("quire") accumulator that is truncated once per dot product.

TPU realisation:
  * weights live in HBM as packed nibbles (2 codes/byte) + blockwise
    scales -> each HBM byte carries 2 sub-octet operands (the SIMD-lane
    packing win, restated as a bandwidth win for the memory-bound side);
  * nibbles are unpacked + dequantized *in VMEM*, immediately before the
    MXU dot — sub-octet data never round-trips through HBM densely;
  * the output tile accumulates across the K grid dimension in an f32
    VMEM scratch (output-stationary: partial sums never leave the "PE"),
    and is cast to the output dtype exactly once, after the last K step
    (the paper's end-of-dot-product quire truncation).

Grid: (M/bm, N/bn, K/bk), K innermost with "arbitrary" semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import get_format

__all__ = ["qmm_kernel_call"]


def _decode_nibbles(codes, fmt):
    """int32 nibble codes (0..15) -> f32 values of a 4-bit format."""
    if fmt.kind == "int":                         # int4: two's complement
        return ((codes ^ 8) - 8).astype(jnp.float32)
    # fp4 / nf4: 16-way codebook as an unrolled compare-select chain on
    # int32 codes — VPU-friendly, no gather
    vals = jnp.zeros(codes.shape, jnp.float32)
    for i, cval in enumerate(fmt.codebook):
        vals = jnp.where(codes == i, jnp.float32(cval), vals)
    return vals


def _scale_rows(vals, scales, rows_per_scale: int):
    """Multiply each run of ``rows_per_scale`` rows by its scale row."""
    r, bn = vals.shape
    vals = vals.reshape(r // rows_per_scale, rows_per_scale, bn)
    return (vals * scales[:, None, :]).reshape(r, bn)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                fmt_name: str, bk: int, sub_block: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fmt = get_format(fmt_name)
    scales = s_ref[...]                           # (bk//sub_block, bn) f32
    x = x_ref[...].astype(jnp.bfloat16)
    if fmt.bits == 4:
        # packed row r holds K rows 2r (low nibble) and 2r+1 (high
        # nibble); the wrapper permuted x's columns within the tile so
        # that its first half multiplies the low nibbles and its second
        # half the high ones — no in-VMEM interleave. Unpacking widens
        # to int32 first: Mosaic does not shift 8-bit vectors on v5e.
        packed = w_ref[...].astype(jnp.int32)     # (bk//2, bn)
        half = bk // 2
        w_lo = _scale_rows(_decode_nibbles(packed & 0xF, fmt), scales,
                           sub_block // 2)
        w_hi = _scale_rows(_decode_nibbles((packed >> 4) & 0xF, fmt), scales,
                           sub_block // 2)
        # MXU dots with f32 accumulate into the output-stationary scratch
        acc_ref[...] += (
            jnp.dot(x[:, :half], w_lo.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
            + jnp.dot(x[:, half:], w_hi.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32))
    else:                                         # int8 / fp8 storage
        w = _scale_rows(w_ref[...].astype(jnp.float32), scales, sub_block)
        acc_ref[...] += jnp.dot(x, w.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)  # quire truncation


@functools.partial(jax.jit, static_argnames=(
    "fmt_name", "sub_block", "bm", "bn", "bk", "out_dtype", "interpret"))
def qmm_kernel_call(x, packed, scales, *, fmt_name: str, sub_block: int,
                    bm: int, bn: int, bk: int, out_dtype=jnp.bfloat16,
                    interpret: bool = False):
    """x:(M,K) @ dequant(packed,scales):(K,N) -> (M,N).

    Preconditions (enforced by kernels.ops): M%bm==0, N%bn==0, K%bk==0,
    bk%sub_block==0, and sub_block even for packed 4-bit formats.
    """
    M, K = x.shape
    fmt = get_format(fmt_name)
    N = packed.shape[-1]
    pack = 2 if fmt.bits == 4 else 1
    if pack == 2:
        # within each K tile, even columns first, then odd columns: the
        # order the kernel's low/high nibble halves consume them
        x = x.reshape(M, K // bk, bk // 2, 2).swapaxes(-1, -2).reshape(M, K)

    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, fmt_name=fmt_name, bk=bk,
                          sub_block=sub_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // pack, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // sub_block, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"qmm_{fmt_name}",
    )(x, packed, scales)
