"""Encoder-decoder translation models (NLLB-200): weights, requests and
the plain reference.

Three things the harness needs for every configuration of this family:

* :func:`init_params`: the weights, drawn on the device from the run's
  key in one jitted call, as the f32 tree the program's ``deploy``
  takes (it quantizes them to the configuration's formats itself);
* :func:`request`: one traffic item as the program's request dict;
* :func:`served_gaps`: the plain reference. It imports nothing of the
  program and takes nothing the program made: it draws the same
  weights from the same key, applies the configuration's stored
  formats itself (blockwise absmax int4/int8, or bf16 rounding; int8
  or bf16 KV storage), and runs the model as the program defines it,
  in f32 at ``HIGHEST`` matmul precision, teacher-forced over a served
  stream. It returns, at every position, by how much the served
  token's logit lies below the reference's best.

The model, as the program defines it (and as the configuration's
``departures`` list against the published NLLB-200): pre-norm RMSNorm
blocks (eps ``norm_eps``) with a final norm on each stack; bidirectional
encoder self-attention and causal decoder self-attention, both with
rotary positions (half-split pairs, ``rope_theta``); cross-attention
without positions; ReLU FFNs; no biases; the tied embedding as the
head. What the serving path stores, the reference stores alike: past
self-KV entries and, after the prefill's first step, the cross-KV pass
through the KV format; the current token attends its own K/V and the
first step its cross K/V before storage.

The control (``control="fp8"``) is the same reference with every matmul
input (linear layers and the head) rounded per token to fp8 e4m3
(absmax scaling), the step below the bf16 activations the
configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_params", "request", "prepare", "logits", "served_gaps",
           "FORMAT_MAX"]

HI = jax.lax.Precision.HIGHEST
# largest code of each symmetric integer format
FORMAT_MAX = {"int4": 7.0, "int8": 127.0}


def init_params(m: dict, key):
    """The f32 parameter tree, in the program's layout, on the device."""
    d, H, Hkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    ff, V = m["d_ff"], m["vocab_size"]
    Le, Ld = m["enc_layers"], m["num_layers"]

    def build(key):
        keys = iter(jax.random.split(key, 32))

        def nrm(shape, std):
            return jax.random.normal(next(keys), shape, jnp.float32) * std

        def attn(L):
            return {"wq": nrm((L, d, H * hd), d ** -0.5),
                    "wk": nrm((L, d, Hkv * hd), d ** -0.5),
                    "wv": nrm((L, d, Hkv * hd), d ** -0.5),
                    "wo": nrm((L, H * hd, d), (H * hd) ** -0.5)}

        def mlp(L):
            return {"w_in": nrm((L, d, ff), d ** -0.5),
                    "w_out": nrm((L, ff, d), ff ** -0.5)}

        def ones(*shape):
            return jnp.ones(shape, jnp.float32)

        return {
            "embedding": nrm((V, d), 0.02),
            "encoder": {"layers": {"attn": attn(Le), "mlp": mlp(Le),
                                   "norm1_scale": ones(Le, d),
                                   "norm2_scale": ones(Le, d)},
                        "norm_f_scale": ones(d)},
            "decoder": {"layers": {"attn": attn(Ld), "cross": attn(Ld),
                                   "mlp": mlp(Ld),
                                   "norm1_scale": ones(Ld, d),
                                   "norm2_scale": ones(Ld, d),
                                   "norm3_scale": ones(Ld, d)},
                        "norm_f_scale": ones(d)},
        }

    return jax.jit(build)(key)


def request(item) -> dict:
    """A traffic item as the program's B=1 request: the source row and
    the target-language code token that prompts the decoder."""
    return {"src_tokens": np.asarray(item.src, np.int32)[None],
            "tgt_in": np.full((1, 1), item.lang, np.int32)}


# ---------------------------------------------------------------------
# stored formats
# ---------------------------------------------------------------------

def _blockwise(w, fmt: str, block: int, axis: int):
    """Blockwise symmetric absmax quantize-dequantize along ``axis``:
    each run of ``block`` values shares scale absmax / max code."""
    if fmt == "bf16":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    top = FORMAT_MAX[fmt]
    x = jnp.moveaxis(w, axis, -1)
    shape = x.shape
    xb = x.reshape(shape[:-1] + (shape[-1] // block, block))
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / top
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xb / safe), -top, top)
    return jnp.moveaxis((q * scale).reshape(shape), -1, axis)


def _store_kv(t, fmt: str):
    """(..., heads, hd) through the KV storage format: int8 with one
    absmax scale per token and head, bf16, or f32."""
    if fmt == "f32":
        return t
    if fmt == "bf16":
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.max(jnp.abs(t), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(t / safe), -127, 127) * safe


def _act_fp8(x):
    """Per-token (last axis) fp8 e4m3 quantize-dequantize, the absmax
    scaled to the format's largest value, 448."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    safe = jnp.where(scale == 0, 1.0, scale)
    return (x / safe).astype(jnp.float8_e4m3fn).astype(jnp.float32) * safe


CONTROLS = {"fp8": _act_fp8}


def prepare(params, formats: dict):
    """The weights as the configuration stores them, in f32: linear
    weights blockwise along their input axis, the embedding along its
    feature axis, norm scales as bf16."""
    g = formats["group"]

    def visit(path, w):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return w.astype(jnp.bfloat16).astype(jnp.float32)
        if "embedding" in name:
            return _blockwise(w, formats["embed"], g, -1)
        return _blockwise(w, formats["weights"], g, -2)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(visit, p))(
        params)


# ---------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(theta) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _softmax(s, mask):
    s = jnp.where(mask, s, -jnp.inf)
    s = s - jnp.max(s, -1, keepdims=True)
    e = jnp.exp(s)
    return e / jnp.sum(e, -1, keepdims=True)


def _forward(m, kv_fmt, control, W, src, src_len, tgt):
    """Teacher-forced logits (T, V) of one request: ``src`` (S,) padded
    past ``src_len``, decoder inputs ``tgt`` (T,)."""
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    G = H // Hkv
    eps, theta = m["norm_eps"], m["rope_theta"]
    act = CONTROLS[control] if control else (lambda x: x)

    def lin(x, w):
        return jnp.matmul(act(x), w, precision=HI)

    def heads(x, n):
        return x.reshape(x.shape[0], n, hd)

    def attend(q, k, v, mask):
        # q (Tq, H, hd), k/v (Tk, Hkv, hd); grouped query heads
        qg = q.reshape(q.shape[0], Hkv, G, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) * hd ** -0.5
        p = _softmax(s, mask)
        return p, jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    E = W["embedding"]
    S, T = src.shape[0], tgt.shape[0]
    spos = jnp.arange(S)
    tpos = jnp.arange(T)
    src_ok = (spos < src_len)[None, :]

    def enc_layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["norm1_scale"], eps)
        q = _rope(heads(lin(h, a["wq"]), H), spos, theta)
        k = _rope(heads(lin(h, a["wk"]), Hkv), spos, theta)
        v = heads(lin(h, a["wv"]), Hkv)
        _, o = attend(q, k, v, src_ok)
        x = x + lin(o.reshape(S, H * hd), a["wo"])
        h = _rms(x, lp["norm2_scale"], eps)
        mp = lp["mlp"]
        return x + lin(jax.nn.relu(lin(h, mp["w_in"])), mp["w_out"]), None

    x, _ = jax.lax.scan(enc_layer, E[src], W["encoder"]["layers"])
    enc = _rms(x, W["encoder"]["norm_f_scale"], eps)

    eye = tpos[:, None] == tpos[None, :]
    causal = tpos[None, :] <= tpos[:, None]
    first = (tpos == 0)[:, None, None]

    def dec_layer(y, lp):
        a = lp["attn"]
        h = _rms(y, lp["norm1_scale"], eps)
        q = _rope(heads(lin(h, a["wq"]), H), tpos, theta)
        k = _rope(heads(lin(h, a["wk"]), Hkv), tpos, theta)
        v = heads(lin(h, a["wv"]), Hkv)
        kq, vq = _store_kv(k, kv_fmt), _store_kv(v, kv_fmt)
        # each position attends the stored entries of earlier positions
        # and its own fresh K/V
        qg = q.reshape(T, Hkv, G, hd)
        s = jnp.where(
            eye, jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI),
            jnp.einsum("qhgd,khd->hgqk", qg, kq, precision=HI)) * hd ** -0.5
        p = _softmax(s, causal)
        pe = jnp.where(eye, p, 0.0)
        o = (jnp.einsum("hgqk,khd->qhgd", p - pe, vq, precision=HI)
             + jnp.einsum("hgqk,khd->qhgd", pe, v, precision=HI))
        y = y + lin(o.reshape(T, H * hd), a["wo"])

        c = lp["cross"]
        h = _rms(y, lp["norm2_scale"], eps)
        cq = heads(lin(h, c["wq"]), H)
        ck = heads(lin(enc, c["wk"]), Hkv)
        cv = heads(lin(enc, c["wv"]), Hkv)
        _, o0 = attend(cq, ck, cv, src_ok)               # the prefill step
        _, o1 = attend(cq, _store_kv(ck, kv_fmt), _store_kv(cv, kv_fmt),
                       src_ok)                           # decode steps
        o = jnp.where(first[..., None], o0, o1)
        y = y + lin(o.reshape(T, H * hd), c["wo"])

        h = _rms(y, lp["norm3_scale"], eps)
        mp = lp["mlp"]
        return y + lin(jax.nn.relu(lin(h, mp["w_in"])), mp["w_out"]), None

    y, _ = jax.lax.scan(dec_layer, E[tgt], W["decoder"]["layers"])
    out = _rms(y, W["decoder"]["norm_f_scale"], eps)
    return lin(out, E.T)


@functools.partial(jax.jit, static_argnames=("m", "kv_fmt", "control"))
def _gaps(W, src, src_len, tgt, served, *, m, kv_fmt, control):
    ref = _forward(dict(m), kv_fmt, False, W, src, src_len, tgt)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return gap
    low = _forward(dict(m), kv_fmt, control, W, src, src_len, tgt)
    pick = jnp.argmax(low, -1)
    return best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def _padded(src, lang, served, pad_src, pad_tgt):
    served = np.asarray(served, np.int32)
    T = len(served)
    src_p = np.zeros(pad_src, np.int32)
    src_p[:len(src)] = src
    tgt = np.zeros(pad_tgt, np.int32)
    tgt[0] = lang
    tgt[1:T] = served[:-1]
    srv = np.zeros(pad_tgt, np.int32)
    srv[:T] = served
    return jnp.asarray(src_p), jnp.int32(len(src)), jnp.asarray(tgt), \
        jnp.asarray(srv), T


def _static(m: dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(W, m: dict, formats: dict, src, lang: int, served,
           control: str = ""):
    """The reference's (or the control's) teacher-forced logits (T, V)
    at every position of one served stream, unpadded."""
    s, n, tgt, _, _ = _padded(src, lang, served, len(src), len(served))
    return _logits(W, s, n, tgt, m=_static(m), kv_fmt=formats["kv"],
                   control=control)


@functools.partial(jax.jit, static_argnames=("m", "kv_fmt", "control"))
def _logits(W, src, src_len, tgt, *, m, kv_fmt, control):
    return _forward(dict(m), kv_fmt, control, W, src, src_len, tgt)


def served_gaps(W, m: dict, formats: dict, src, lang: int, served,
                pad_src: int, pad_tgt: int, control: str = ""):
    """Per-position gap (f32, one per served token) between the
    reference's best logit and its logit for the served token; with
    ``control`` ("fp8") the token that control puts first
    takes the served token's place. Sources and streams are padded to
    ``pad_src`` / ``pad_tgt`` so that one compiled program serves
    every request."""
    s, n, tgt, srv, T = _padded(src, lang, served, pad_src, pad_tgt)
    gap = _gaps(W, s, n, tgt, srv, m=_static(m), kv_fmt=formats["kv"],
                control=control)
    return np.asarray(gap)[:T]
