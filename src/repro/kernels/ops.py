"""Public, shape-safe wrappers around the Pallas kernels.

Handles tiling choices, padding to tile multiples, layout transforms, and
interpret-mode selection (kernels execute in Python via interpret=True on
CPU — correctness validation; on TPU they compile to Mosaic).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..core.formats import get_format
from ..core.qtensor import QTensor
from . import decode_attn as _da
from . import fasst as _fasst
from . import paged_attn as _pa
from . import qmm as _qmm

__all__ = ["qmm", "fasst", "fasst_softmax", "decode_attention",
           "paged_decode_attention", "quantize_kv", "interpret_mode"]


@functools.lru_cache(maxsize=1)
def interpret_mode() -> bool:
    """Pallas interpret=True everywhere except a real TPU backend.

    ``REPRO_PALLAS_INTERPRET=1`` forces interpret mode regardless of
    backend (CI's kernels-interpret job sets it so kernel regressions
    fail PRs without a TPU runner).
    """
    if os.environ.get("REPRO_PALLAS_INTERPRET", "") == "1":
        return True
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_tile(dim: int, preferred: int, multiple: int = 1) -> int:
    """Largest tile <= preferred that divides dim and is a multiple of m."""
    t = min(preferred, dim)
    while t > multiple:
        if dim % t == 0 and t % multiple == 0:
            return t
        t -= multiple
    return multiple if dim % multiple == 0 else dim


def qmm(x: jnp.ndarray, w: QTensor, *, compute_dtype=jnp.bfloat16,
        bm: int = 128, bn: int = 256, bk: int = 512):
    """x @ dequant(w) via the fused dequant-matmul kernel.

    Accepts x of shape (..., K); w must be an unbatched (K, N) QTensor
    quantized along q_axis=-2.
    """
    fmt = get_format(w.fmt)
    # derive dims from the runtime payload (robust to lax.scan slicing)
    K = w.data.shape[-2] * (2 if fmt.bits == 4 else 1)
    N = w.data.shape[-1]
    sub_block = K // w.scales_shape[-2]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]

    if fmt.bits == 4 and sub_block % 2:
        raise ValueError(f"packed {w.fmt} qmm needs an even block size, "
                         f"got {sub_block}")
    interpret = interpret_mode()
    if N % 128 and not interpret:
        # a TPU block's lane dim must be a multiple of 128 (or all of
        # N, which would not fit VMEM for a vocab-sized head)
        raise ValueError(f"qmm on TPU needs N % 128 == 0, got N={N}")
    bk = _pick_tile(K, bk, multiple=sub_block)
    bn = _pick_tile(N, bn, multiple=128 if N % 128 == 0 else 1)
    Mp = _round_up(max(M, 1), bm) if M % bm else M
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))

    y = _qmm.qmm_kernel_call(
        x2.astype(compute_dtype), w.data, w.block_scales(),
        fmt_name=w.fmt, sub_block=sub_block, bm=min(bm, Mp), bn=bn, bk=bk,
        out_dtype=compute_dtype, interpret=interpret)
    return y[:M].reshape(*lead, N)


# VMEM bytes one fasst block may take: Mosaic's scoped VMEM limit is
# 16 MiB, and a (256, 8192) block with its double buffers and f32
# temporaries does not fit in it
_FASST_VMEM_BUDGET = 8 * 2**20


def _fasst_rows(C: int, in_dtype, out_dtype) -> int:
    """Most rows (a multiple of 8) whose block fits the VMEM budget:
    double-buffered input and output tiles plus two f32 temporaries."""
    per_row = C * (2 * (jnp.dtype(in_dtype).itemsize
                        + jnp.dtype(out_dtype).itemsize) + 8)
    return max(8, _FASST_VMEM_BUDGET // per_row // 8 * 8)


def fasst(x: jnp.ndarray, mode: str, *, out_dtype=None, bm: int = 256):
    """Reconfigurable NAF (paper's FASST): elementwise over any shape."""
    shape = x.shape
    C = shape[-1]
    x2 = x.reshape(-1, C)
    M = x2.shape[0]
    bm = _pick_tile(M, min(bm, _fasst_rows(C, x.dtype, out_dtype or x.dtype)))
    if M % bm:
        pad = _round_up(M, bm) - M
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _fasst.fasst_act_call(x2, mode=mode, bm=bm,
                              out_dtype=out_dtype or x.dtype,
                              interpret=interpret_mode())
    return y[:M].reshape(shape)


def fasst_softmax(x: jnp.ndarray, *, scale: float = 1.0, valid_cols: int = -1,
                  out_dtype=None, bm: int = 8):
    """Fused row-wise softmax over the last axis."""
    shape = x.shape
    C = shape[-1]
    x2 = x.reshape(-1, C)
    M = x2.shape[0]
    bm = _pick_tile(M, bm)
    if M % bm:
        pad = _round_up(M, bm) - M
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _fasst.fasst_softmax_call(x2, bm=bm, valid_cols=valid_cols,
                                  scale=scale, out_dtype=out_dtype or x.dtype,
                                  interpret=interpret_mode())
    return y[:M].reshape(shape)


def quantize_kv(kv: jnp.ndarray):
    """Per-(token, head) int8 quantization for KV caches (see ref.py)."""
    from .ref import quantize_kv_ref
    return quantize_kv_ref(kv)


def decode_attention(q, k_codes, k_scales, v_codes, v_scales, lengths, *,
                     sm_scale: float | None = None, bs: int = 128,
                     out_dtype=jnp.bfloat16):
    """GQA decode attention against an int8 KV cache.

    q (B, H, d); k/v codes (B, S, Hkv, d) int8; scales (B, S, Hkv) f32;
    lengths (B,) int32. Returns (B, H, d).
    """
    B, H, d = q.shape
    S, Hkv = k_codes.shape[1], k_codes.shape[2]
    G = H // Hkv
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5

    qg = q.reshape(B, Hkv, G, d)
    Gp = _round_up(G, 8)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    bs = _pick_tile(S, bs, multiple=128 if S % 128 == 0 else 1)
    kt = jnp.transpose(k_codes, (0, 2, 1, 3))   # (B,Hkv,S,d)
    vt = jnp.transpose(v_codes, (0, 2, 1, 3))
    kst = jnp.transpose(k_scales, (0, 2, 1))    # (B,Hkv,S)
    vst = jnp.transpose(v_scales, (0, 2, 1))

    out = _da.decode_attn_call(
        qg, kt, kst, vt, vst, lengths.astype(jnp.int32), bs=bs,
        sm_scale=float(sm_scale), out_dtype=out_dtype,
        interpret=interpret_mode())
    return out[:, :, :G, :].reshape(B, H, d)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           k_scales=None, v_scales=None,
                           sm_scale: float | None = None,
                           out_dtype=jnp.bfloat16):
    """GQA decode attention against a block-paged KV cache.

    q (B, H, d); k/v pages (P, ps, Hkv, d) — int8 codes with
    (P, ps, Hkv) f32 scales, or bf16 with scales=None; block_tables
    (B, maxp) int32 page ids (out-of-chain entries must point at a
    page that ``lengths`` masks out, e.g. the reserved trash page);
    lengths (B,) int32. Returns (B, H, d).
    """
    B, H, d = q.shape
    Hkv = k_pages.shape[2]
    G = H // Hkv
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5

    qg = q.reshape(B, Hkv, G, d)
    Gp = _round_up(G, 8)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    kt = jnp.transpose(k_pages, (0, 2, 1, 3))   # (P, Hkv, ps, d)
    vt = jnp.transpose(v_pages, (0, 2, 1, 3))
    kst = None if k_scales is None else jnp.transpose(k_scales, (0, 2, 1))
    vst = None if v_scales is None else jnp.transpose(v_scales, (0, 2, 1))

    out = _pa.paged_attn_call(
        qg, kt, kst, vt, vst, block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32), sm_scale=float(sm_scale),
        out_dtype=out_dtype, interpret=interpret_mode())
    return out[:, :, :G, :].reshape(B, H, d)
