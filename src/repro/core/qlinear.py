"""Quantized linear algebra front-end.

Every matmul in the model zoo routes through :func:`qmatmul`, which
dispatches on the weight's storage and the activation format:

  * plain array            -> bf16 MXU matmul (baseline);
  * QTensor, act bf16      -> fused dequant-matmul (w4a16 / w8a16 / fp8):
                              XLA path dequantizes next to the dot (HBM
                              reads stay sub-octet); the Pallas path
                              (kernels/qmm.py) does it in VMEM tiles;
  * QTensor int8 + act int8-> integer matmul on the int8 MXU mode with
                              per-token x per-channel rescale (the TPU
                              realisation of the paper's 6xINT4/
                              3xFP8 SIMD MAC lanes — see DESIGN.md);
  * act int8/fp8 otherwise -> the activations are genuinely quantized
                              (absmax grid / e4m3 codes) then widened
                              back for a bf16-accumulate matmul — the
                              software twin of the paper's narrow-
                              multiply / wide-accumulate RMMEC lanes.
                              An ``a8`` spec never silently runs bf16
                              activations.

Static per-site activation scales (core.calibration) arrive via
``act_scale``; ``None`` means dynamic per-token quantization.

QLoRA adapters attached to the QTensor contribute the trainable low-rank
update: y += (x @ A) @ B * (alpha / r), with the base frozen via
stop_gradient (paper §III: QLoRA keeps original quantized weights fixed).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .qtensor import QTensor

__all__ = ["qmatmul", "embed_lookup", "quantize_activations",
           "quantize_activations_int8", "int8_mac_eligible",
           "act_quant_eligible"]


def int8_mac_eligible(w: Any) -> bool:
    """True when ``w`` routes through the integer-MAC w8a8 path: int8
    storage with per-channel scales (one K-block). The single source of
    this predicate — activation calibration keys on it so calibrated
    scales observe exactly the matmuls they will be applied to."""
    return (isinstance(w, QTensor) and w.fmt == "int8"
            and w.block_scales().shape[-2] == 1)


def act_quant_eligible(w: Any) -> bool:
    """True when a matmul against ``w`` quantizes its activations under
    an act-quantizing spec (a8 / afp8) — the sites the calibration
    collector (Ctx.act_collector) observes. Every quantized weight
    qualifies: eligible formats take the integer-MAC path, the rest
    fake-quantize their activations (see qmatmul)."""
    return isinstance(w, QTensor)


def quantize_activations(x: jnp.ndarray, fmt: str = "int8", scale=None):
    """Symmetric quantization of activations to int8 or fp8 (e4m3).

    ``scale=None`` (default) is the dynamic per-token path: each token
    row gets its own absmax-derived scale. A static ``scale`` (a
    per-site scalar from ``core.calibration``, the paper's calibrated
    PTQ deployment) skips the runtime absmax reduction — outliers beyond
    the calibrated range saturate at the format edge instead of
    stretching the grid. Returns ``(codes, scale)``.
    """
    if fmt == "int8":
        max_code = 127.0
    elif fmt == "fp8":
        max_code = 448.0
    else:
        raise ValueError(f"activation format must be int8 | fp8, got {fmt!r}")
    if scale is None:
        absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                         keepdims=True)
        scale = jnp.where(absmax == 0, 1.0, absmax / max_code)
    else:
        scale = jnp.asarray(scale, jnp.float32)
    if fmt == "int8":
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    else:
        q = (jnp.clip(x.astype(jnp.float32) / scale, -448.0, 448.0)
             ).astype(jnp.float8_e4m3fn)
    return q, scale.astype(jnp.float32)


def quantize_activations_int8(x: jnp.ndarray, scale=None):
    """Legacy alias for ``quantize_activations(x, "int8", scale)``."""
    return quantize_activations(x, "int8", scale)


def _lora_term(x, w: QTensor, compute_dtype):
    if w.lora_a is None:
        return None
    r = w.lora_a.shape[-1]
    scaling = w.lora_alpha / r
    xa = jnp.matmul(x.astype(compute_dtype), w.lora_a.astype(compute_dtype))
    return jnp.matmul(xa, w.lora_b.astype(compute_dtype)) * scaling


def _int8_path(x, w: QTensor, compute_dtype, act_scale=None):
    """w8a8 integer matmul. Requires per-channel weight scales (1 K-block)."""
    if not int8_mac_eligible(w):
        return None                    # blockwise int8: fake-quant fallback
    scales = w.block_scales()          # (..., 1, N)
    xq, sx = quantize_activations(x, "int8", act_scale)
    out = jax.lax.dot_general(
        xq, w.data,
        dimension_numbers=(((x.ndim - 1,), (w.data.ndim - 2,)), ((), ())),
        preferred_element_type=jnp.int32)
    sw = jnp.squeeze(scales, axis=-2)  # (..., N)
    return (out.astype(jnp.float32) * sx * sw).astype(compute_dtype)


def _fake_quant_act(x, fmt: str, act_scale, compute_dtype):
    """Quantize-then-widen activations for formats/weights with no native
    MAC route here: the quantization error is real (the quality signal
    the eval grid measures), the accumulate stays wide (paper's
    quire-style accumulation)."""
    xq, sx = quantize_activations(x, fmt, act_scale)
    return (xq.astype(jnp.float32) * sx).astype(compute_dtype)


def qmatmul(
    x: jnp.ndarray,
    w: Any,
    *,
    act: str = "bf16",
    compute_dtype=jnp.bfloat16,
    impl: str = "xla",
    act_scale=None,
) -> jnp.ndarray:
    """y = x @ w for plain or quantized ``w`` (last-2-axis contraction).

    ``act_scale``: optional calibrated static scale for the int8/fp8
    activation paths (see quantize_activations); ignored elsewhere.
    """
    if not isinstance(w, QTensor):
        return jnp.matmul(x.astype(compute_dtype), w.astype(compute_dtype))

    lora = _lora_term(x, w, compute_dtype)

    y = None
    if act == "int8" and w.fmt == "int8":
        y = _int8_path(x, w, compute_dtype, act_scale)
    if y is None:
        if act in ("int8", "fp8"):
            # no integer/native route for this (weight fmt, act fmt)
            # pair: quantize the activations anyway — an act-quantizing
            # spec must never silently run bf16 activations
            x = _fake_quant_act(x, act, act_scale, compute_dtype)
        if impl == "pallas" and w.fmt in ("int4", "fp4", "nf4") \
                and w.data.ndim == 2 and w.q_axis == -2:
            # (K, N) weights quantized along K only: embedding-style
            # tables (q_axis=-1, e.g. an untied lm_head) stay on XLA
            from ..kernels import ops as kops  # lazy: avoid import cycle
            y = kops.qmm(x, w, compute_dtype=compute_dtype)
        else:
            wd = jax.lax.stop_gradient(w.dequantize(compute_dtype))
            y = jnp.matmul(x.astype(compute_dtype), wd)

    if lora is not None:
        y = y + lora.astype(y.dtype)
    return y


def embed_lookup(table: Any, ids: jnp.ndarray, compute_dtype=jnp.bfloat16):
    """Embedding gather with row-wise dequantization for QTensor tables."""
    if not isinstance(table, QTensor):
        return jnp.take(table, ids, axis=0).astype(compute_dtype)
    rows = jnp.take(table.data, ids, axis=0)
    scales = jnp.take(table.block_scales(), ids, axis=0)
    from .quantize import dequantize_blockwise
    return dequantize_blockwise(rows, scales, table.fmt, q_axis=-1,
                                out_dtype=compute_dtype)
