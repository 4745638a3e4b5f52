"""Operations and bytes that a decode step of the enc-dec model needs.

Worked out from the configuration's sizes and the lengths actually
served, never from the program's caps: a padded slot, a masked position
or a page reserved past a request's length counts for nothing. These
are the least work the algorithm needs, so a roofline share built on
them stays at or under 100%.

One decode micro-step of a live request at output position ``j``
(``j`` = 1 for the first token decoded after the prefill's) runs every
decoder layer once for one token: self-attention over the ``j``
cached positions plus its own, cross-attention over its ``src``
encoder positions, a two-layer FFN, and the tied head over the whole
vocabulary. The step reads each decoder weight and the tied head once
however many requests it serves, plus the self-KV of each live
request's cached positions and the cross-KV of its source, and writes
one new self-KV entry per request and layer.
"""

from __future__ import annotations

import numpy as np

__all__ = ["FORMAT_BYTES", "decoder_linear_params", "head_params",
           "token_flops", "step_weight_bytes", "token_kv_bytes"]

# stored bytes per value, and bytes of scale per value, of each format
# the configurations use (blockwise scales are f32: 4 bytes per block)
FORMAT_BYTES = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}


def _scale_bytes(fmt: str, block: int) -> float:
    return 0.0 if fmt == "bf16" else 4.0 / block


def decoder_linear_params(m: dict) -> int:
    """Weights of the decoder's linear layers that a decode step reads:
    self q/k/v/o, cross q/o (cross k/v run once, at the prefill), FFN."""
    d, H, hd, ff = m["d_model"], m["num_heads"], m["head_dim"], m["d_ff"]
    Hkv = m["num_kv_heads"]
    attn = d * H * hd * 2 + d * Hkv * hd * 2
    cross = d * H * hd * 2
    return m["num_layers"] * (attn + cross + 2 * d * ff)


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"]


def token_flops(m: dict, j, src):
    """Model FLOPs of decoding one token at position ``j`` against a
    source of ``src`` positions (arrays broadcast): 2 per multiply-add
    of every linear layer and the head, plus the attention products."""
    j = np.asarray(j, np.float64)
    src = np.asarray(src, np.float64)
    L, H, hd = m["num_layers"], m["num_heads"], m["head_dim"]
    dense = 2.0 * (decoder_linear_params(m) + head_params(m))
    # q.k and p.v over (j + 1) self positions and src cross positions
    attn = 2.0 * 2.0 * L * H * hd * ((j + 1.0) + src)
    return dense + attn


def step_weight_bytes(m: dict, q: dict) -> float:
    """Bytes one decode micro-step reads for weights, at their stored
    width with their scales: decoder linears, the tied head, norms."""
    w, e, g = q["weights"], q["embed"], q["group"]
    lin = decoder_linear_params(m) * (FORMAT_BYTES[w] + _scale_bytes(w, g))
    head = head_params(m) * (FORMAT_BYTES[e] + _scale_bytes(e, g))
    norms = (3 * m["num_layers"] + 1) * m["d_model"] * 2.0
    return lin + head + norms


def token_kv_bytes(m: dict, q: dict, j, src):
    """KV bytes one live request adds to a micro-step at position ``j``:
    read ``j`` cached self positions and ``src`` cross positions, write
    one self position, in every layer, at the KV's stored width (int8
    keeps one f32 scale per token and head)."""
    j = np.asarray(j, np.float64)
    src = np.asarray(src, np.float64)
    L, Hkv, hd = m["num_layers"], m["num_kv_heads"], m["head_dim"]
    kv = q["kv"]
    per_pos = 2.0 * Hkv * (hd * FORMAT_BYTES[kv]
                           + (4.0 if kv != "bf16" else 0.0))
    return L * per_pos * (j + src + 1.0)
