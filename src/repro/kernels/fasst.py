"""FASST — one reconfigurable non-linear activation kernel (paper Figs. 7-8).

The paper's FASST unit is a single CORDIC datapath reused for SoftMax,
sigmoid, tanh, ReLU (+ GeLU/SiLU/SELU variants) at FP8/BF16 I/O, because
NAFs are up to 60% of NLLB's op count and dedicated per-function hardware
is wasteful. TPU adaptation (see DESIGN.md): the VPU has fast
transcendentals, so iterative CORDIC would be a de-optimisation — we keep
the *architecture* (one kernel, a static mode switch, low-precision I/O,
f32 internal math) and drop the gate-level algorithm.

Two entry points:
  * fasst_act_call   — elementwise NAF, mode in MODES;
  * fasst_softmax_call — fused row-wise softmax (max-sub / exp / norm in
    one VMEM pass; optional column masking for padded rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["MODES", "fasst_act_call", "fasst_softmax_call"]

MODES = ("relu", "sigmoid", "tanh", "gelu", "silu", "squared_relu", "selu",
         "identity")


def _naf(x: jnp.ndarray, mode: str) -> jnp.ndarray:
    """The shared NAF datapath, f32 in/out."""
    if mode == "relu":
        return jnp.maximum(x, 0.0)
    if mode == "sigmoid":
        return jax.nn.sigmoid(x)
    if mode == "tanh":
        return jnp.tanh(x)
    if mode == "gelu":                       # tanh approximation (as in BERT HW)
        c = jnp.float32(0.7978845608028654)  # sqrt(2/pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
    if mode == "silu":
        return x * jax.nn.sigmoid(x)
    if mode == "squared_relu":               # Primer / nemotron-4
        r = jnp.maximum(x, 0.0)
        return r * r
    if mode == "selu":
        alpha, lam = 1.6732632423543772, 1.0507009873554805
        return lam * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1.0))
    if mode == "identity":
        return x
    raise ValueError(f"unknown NAF mode {mode!r}")


def _act_kernel(x_ref, o_ref, *, mode: str):
    o_ref[...] = _naf(x_ref[...].astype(jnp.float32), mode).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "bm", "out_dtype",
                                             "interpret"))
def fasst_act_call(x, *, mode: str, bm: int, out_dtype=None,
                   interpret: bool = False):
    """Elementwise NAF over a (M, C) array; M % bm == 0."""
    M, C = x.shape
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        functools.partial(_act_kernel, mode=mode),
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"fasst_{mode}",
    )(x)


def _softmax_kernel(x_ref, o_ref, *, valid_cols: int, scale: float):
    x = x_ref[...].astype(jnp.float32) * scale
    C = x.shape[-1]
    if valid_cols < C:  # mask padding columns
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        x = jnp.where(col < valid_cols, x, -jnp.inf)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o_ref[...] = (e / s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "valid_cols", "scale",
                                             "out_dtype", "interpret"))
def fasst_softmax_call(x, *, bm: int, valid_cols: int = -1, scale: float = 1.0,
                       out_dtype=None, interpret: bool = False):
    """Fused row softmax over (M, C); M % bm == 0; rows fit VMEM."""
    M, C = x.shape
    out_dtype = out_dtype or x.dtype
    vc = C if valid_cols < 0 else valid_cols
    return pl.pallas_call(
        functools.partial(_softmax_kernel, valid_cols=vc, scale=scale),
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fasst_softmax",
    )(x)
