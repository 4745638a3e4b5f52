"""Encoder-decoder transformer: NLLB-600M (the paper's model) + whisper.

Paper §II-A: distilled NLLB-200 600M — pre-norm residual encoder/decoder
stacks, multi-head attention, two-layer FFNs, per-language tokenizers,
many-to-many translation driven by target-language code tokens; the MoE
variant (Fig. 3b) swaps the FFN for top-k experts. Whisper-base reuses the
same skeleton with a stub conv frontend (input_specs feeds precomputed
frame embeddings) and cross-attention from the decoder.

Every layer's work sits in a ``jax.named_scope`` named as its ``Ctx.dot``
sites are (``embed``, ``enc.attn``, ``enc.ffn``, ``dec.cross.kv``,
``dec.attn``, ``dec.cross``, ``dec.ffn``, ``head``), and the serving
decoder's layer loop in ``dec.layers``, which names what the loop itself
does: slicing each layer's weights and caches out of their stacks and
stacking the written caches back. A profile of the compiled programs
then lays each device operation to its layer (the scopes are metadata:
the computation is the same without them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.qlinear import embed_lookup
from ..core.qtensor import maybe_dequantize
from ..parallel import hint, hint_pick
from . import moe as moe_mod
from .layers import (Ctx, attention_init, attn_apply, decode_attn_apply,
                     mlp, mlp_init, rms_norm)
from .transformer import (_commit_decode_position, _dense_kv, _fp8_token_kv,
                          _quantize_token_kv, _scatter_tokens, paged_attn,
                          paged_view)

__all__ = ["encdec_init", "encdec_encode", "encdec_forward",
           "encdec_init_cache", "encdec_init_paged_cache", "encdec_prefill",
           "encdec_decode_step"]


def _enc_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    p = {
        "attn": attention_init(k1, cfg.d_model, cfg.num_heads,
                               cfg.num_kv_heads, cfg.head_dim),
        "norm1_scale": jnp.ones((cfg.d_model,), jnp.float32),
        "norm2_scale": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.moe is not None:   # paper Fig. 3b: MoE encoder variant
        p["moe"] = moe_mod.moe_init(k2, cfg.d_model, cfg.d_ff,
                                    cfg.moe.num_experts, cfg.mlp_act)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act)
    return p


def _dec_layer_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "attn": attention_init(k1, cfg.d_model, cfg.num_heads,
                               cfg.num_kv_heads, cfg.head_dim),
        "cross": attention_init(k2, cfg.d_model, cfg.num_heads,
                                cfg.num_kv_heads, cfg.head_dim),
        "norm1_scale": jnp.ones((cfg.d_model,), jnp.float32),
        "norm2_scale": jnp.ones((cfg.d_model,), jnp.float32),
        "norm3_scale": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_init(k3, cfg.d_model, cfg.d_ff,
                                    cfg.moe.num_experts, cfg.mlp_act)
    else:
        p["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.mlp_act)
    return p


def encdec_init(key, cfg):
    ke, k1, k2, kh = jax.random.split(key, 4)
    enc_keys = jax.random.split(k1, cfg.enc_layers)
    dec_keys = jax.random.split(k2, cfg.num_layers)
    params = {
        "embedding": jax.random.normal(
            ke, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02,
        "encoder": {
            "layers": jax.vmap(lambda k: _enc_layer_init(k, cfg))(enc_keys),
            "norm_f_scale": jnp.ones((cfg.d_model,), jnp.float32)},
        "decoder": {
            "layers": jax.vmap(lambda k: _dec_layer_init(k, cfg))(dec_keys),
            "norm_f_scale": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            kh, (cfg.d_model, cfg.vocab_size), jnp.float32) * cfg.d_model ** -0.5
    return params


def encdec_encode(ctx: Ctx, params, cfg, src_tokens=None, frames=None,
                  remat: bool = False):
    """Bidirectional encoder. src_tokens (B,Se) or frames (B,F,d) (audio)."""
    with jax.named_scope("embed"):
        if frames is not None:
            x = frames.astype(ctx.compute_dtype)      # stub conv frontend
        else:
            x = embed_lookup(params["embedding"], src_tokens,
                             ctx.compute_dtype)
    x = hint(x, "batch", None, None)
    B, Se, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(x, lp):
        with jax.named_scope("enc.attn"):
            h = rms_norm(x, lp["norm1_scale"], cfg.norm_eps)
            y, _ = attn_apply(ctx, lp["attn"], h, positions,
                              num_heads=cfg.num_heads,
                              num_kv_heads=cfg.num_kv_heads,
                              head_dim=cfg.head_dim, causal=False, window=0,
                              rope_theta=cfg.rope_theta,
                              norm_eps=cfg.norm_eps, site="enc.attn")
            x = x + y
        with jax.named_scope("enc.ffn"):
            h = rms_norm(x, lp["norm2_scale"], cfg.norm_eps)
            if cfg.moe is not None:
                y, _ = moe_mod.moe_apply(
                    ctx, lp["moe"], h, top_k=cfg.moe.top_k,
                    capacity_factor=cfg.moe.capacity_factor,
                    act=cfg.mlp_act, parallel_mode=cfg.moe.parallel_mode,
                    dispatch_groups=cfg.moe.dispatch_groups)
            else:
                y = mlp(ctx, lp["mlp"], h, cfg.mlp_act, site="enc.ffn")
            x = x + y
        return hint_pick(x, ("batch", "model", None),
                         ("batch", None, None)), None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, params["encoder"]["layers"])
    return rms_norm(x, params["encoder"]["norm_f_scale"], cfg.norm_eps)


def _dec_layer(ctx, cfg, lp, x, positions, enc_kv, collect_kv):
    """enc_kv = (k, v, enc_positions) precomputed cross K/V."""
    with jax.named_scope("dec.attn"):
        h = rms_norm(x, lp["norm1_scale"], cfg.norm_eps)
        y, kv = attn_apply(ctx, lp["attn"], h, positions,
                           num_heads=cfg.num_heads,
                           num_kv_heads=cfg.num_kv_heads,
                           head_dim=cfg.head_dim, causal=True, window=0,
                           rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                           site="dec.attn")
        x = x + y
    with jax.named_scope("dec.cross"):
        h = rms_norm(x, lp["norm2_scale"], cfg.norm_eps)
        y, _ = attn_apply(ctx, lp["cross"], h, positions,
                          num_heads=cfg.num_heads,
                          num_kv_heads=cfg.num_kv_heads,
                          head_dim=cfg.head_dim, causal=False, window=0,
                          kv_override=enc_kv, use_rope=False,
                          norm_eps=cfg.norm_eps, site="dec.cross")
        x = x + y
    with jax.named_scope("dec.ffn"):
        h = rms_norm(x, lp["norm3_scale"], cfg.norm_eps)
        if cfg.moe is not None:
            y, aux = moe_mod.moe_apply(
                ctx, lp["moe"], h, top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor, act=cfg.mlp_act,
                parallel_mode=cfg.moe.parallel_mode,
                dispatch_groups=cfg.moe.dispatch_groups)
        else:
            y, aux = (mlp(ctx, lp["mlp"], h, cfg.mlp_act, site="dec.ffn"),
                      jnp.zeros((), jnp.float32))
        x = x + y
    return hint_pick(x, ("batch", "model", None),
                     ("batch", None, None)), aux, kv


def _cross_kv(ctx, lp, cfg, enc_out):
    """Per-layer cross-attention K/V from encoder output."""
    B, Se, _ = enc_out.shape
    with jax.named_scope("dec.cross.kv"):
        k = ctx.dot(enc_out, lp["cross"]["wk"], site="dec.cross.kv").reshape(
            B, Se, cfg.num_kv_heads, cfg.head_dim)
        v = ctx.dot(enc_out, lp["cross"]["wv"], site="dec.cross.kv").reshape(
            B, Se, cfg.num_kv_heads, cfg.head_dim)
    return k, v


def _head(ctx, params, cfg, x):
    """The decoder's final norm and the (tied) head."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["decoder"]["norm_f_scale"], cfg.norm_eps)
        if cfg.tie_embeddings:
            w = maybe_dequantize(params["embedding"], ctx.compute_dtype)
            logits = jnp.einsum("bsd,vd->bsv", x.astype(ctx.compute_dtype),
                                w)
        else:
            logits = ctx.dot(x, params["lm_head"], site="head")
        return hint_pick(logits.astype(jnp.float32),
                         ("batch", "model", None), ("batch", None, "model"))


def encdec_forward(ctx: Ctx, params, cfg, tgt_tokens, src_tokens=None,
                   frames=None, remat: bool = False):
    """Teacher-forced decoder pass. Returns (logits, aux_loss)."""
    enc_out = encdec_encode(ctx, params, cfg, src_tokens, frames, remat)
    B, Sd = tgt_tokens.shape
    Se = enc_out.shape[1]
    with jax.named_scope("embed"):
        x = embed_lookup(params["embedding"], tgt_tokens, ctx.compute_dtype)
    x = hint(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32), (B, Sd))
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(carry, lp):
        x, aux = carry
        k, v = _cross_kv(ctx, lp, cfg, enc_out)
        x, aux_l, _ = _dec_layer(ctx, cfg, lp, x, positions,
                                 (k, v, enc_pos), False)
        return (x, aux + aux_l), None

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               params["decoder"]["layers"])
    return _head(ctx, params, cfg, x), aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def encdec_init_cache(cfg, batch: int, max_len: int, enc_len: int,
                      kv_dtype: str = "bf16"):
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cache = {
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
        # valid cross-attention length per slot: requests whose source is
        # shorter than the allocated enc_len mask the tail instead of
        # forcing every admitted request to share one source length
        "cross_len": jnp.full((batch,), enc_len, jnp.int32),
    }
    if kv_dtype == "int8":
        # the paper's quantization applied to BOTH self and cross caches
        # (SS Perf iteration on the whisper decode cell: the static cross
        # cache is read every step and dominated decode bytes)
        cache.update(
            k_codes=jnp.zeros((L, batch, max_len, Hkv, hd), jnp.int8),
            k_scales=jnp.zeros((L, batch, max_len, Hkv), jnp.float32),
            v_codes=jnp.zeros((L, batch, max_len, Hkv, hd), jnp.int8),
            v_scales=jnp.zeros((L, batch, max_len, Hkv), jnp.float32),
            cross_k_codes=jnp.zeros((L, batch, enc_len, Hkv, hd), jnp.int8),
            cross_k_scales=jnp.zeros((L, batch, enc_len, Hkv), jnp.float32),
            cross_v_codes=jnp.zeros((L, batch, enc_len, Hkv, hd), jnp.int8),
            cross_v_scales=jnp.zeros((L, batch, enc_len, Hkv), jnp.float32))
        return cache
    if kv_dtype == "fp8":
        # e4m3 codes + per-(token, head) f32 scales, self AND cross —
        # same layout as int8 but with float8 storage ("k"/"v" keys so
        # the fp8 path is "k_scales present, k_codes absent")
        f8 = jnp.float8_e4m3fn
        cache.update(
            k=jnp.zeros((L, batch, max_len, Hkv, hd), f8),
            k_scales=jnp.zeros((L, batch, max_len, Hkv), jnp.float32),
            v=jnp.zeros((L, batch, max_len, Hkv, hd), f8),
            v_scales=jnp.zeros((L, batch, max_len, Hkv), jnp.float32),
            cross_k=jnp.zeros((L, batch, enc_len, Hkv, hd), f8),
            cross_k_scales=jnp.zeros((L, batch, enc_len, Hkv), jnp.float32),
            cross_v=jnp.zeros((L, batch, enc_len, Hkv, hd), f8),
            cross_v_scales=jnp.zeros((L, batch, enc_len, Hkv), jnp.float32))
        return cache
    dt = jnp.float32 if kv_dtype == "f32" else jnp.bfloat16
    cache.update(
        cross_k=jnp.zeros((L, batch, enc_len, Hkv, hd), dt),
        cross_v=jnp.zeros((L, batch, enc_len, Hkv, hd), dt),
        k=jnp.zeros((L, batch, max_len, Hkv, hd), dt),
        v=jnp.zeros((L, batch, max_len, Hkv, hd), dt))
    return cache


def encdec_prefill(ctx: Ctx, params, cfg, cache, tgt_tokens, src_tokens=None,
                   frames=None, lengths=None):
    """Encode source, run decoder prompt, fill self+cross caches."""
    enc_out = encdec_encode(ctx, params, cfg, src_tokens, frames)
    B, Sd = tgt_tokens.shape
    Se = enc_out.shape[1]
    with jax.named_scope("embed"):
        x = embed_lookup(params["embedding"], tgt_tokens, ctx.compute_dtype)
    positions = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32), (B, Sd))
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(carry, lp):
        x, = carry
        ck, cv = _cross_kv(ctx, lp, cfg, enc_out)
        x, _, kv = _dec_layer(ctx, cfg, lp, x, positions,
                              (ck, cv, enc_pos), True)
        return (x,), (kv[0], kv[1], ck, cv)

    with jax.named_scope("dec.layers"):
        (x,), (ks, vs, cks, cvs) = jax.lax.scan(
            body, (x,), params["decoder"]["layers"])
    logits = _head(ctx, params, cfg, x)

    lens = lengths if lengths is not None else jnp.full((B,), Sd, jnp.int32)
    new_cache = dict(cache)
    if "k_codes" in cache:
        kc, ksc = _quantize_token_kv(ks)
        vc, vsc = _quantize_token_kv(vs)
        new_cache["k_codes"] = cache["k_codes"].at[:, :, :Sd].set(kc)
        new_cache["k_scales"] = cache["k_scales"].at[:, :, :Sd].set(ksc)
        new_cache["v_codes"] = cache["v_codes"].at[:, :, :Sd].set(vc)
        new_cache["v_scales"] = cache["v_scales"].at[:, :, :Sd].set(vsc)
        ckc, cksc = _quantize_token_kv(cks)
        cvc, cvsc = _quantize_token_kv(cvs)
        new_cache["cross_k_codes"], new_cache["cross_k_scales"] = ckc, cksc
        new_cache["cross_v_codes"], new_cache["cross_v_scales"] = cvc, cvsc
    elif "k_scales" in cache:   # fp8 self + cross caches
        kc, ksc = _fp8_token_kv(ks)
        vc, vsc = _fp8_token_kv(vs)
        new_cache["k"] = cache["k"].at[:, :, :Sd].set(kc)
        new_cache["k_scales"] = cache["k_scales"].at[:, :, :Sd].set(ksc)
        new_cache["v"] = cache["v"].at[:, :, :Sd].set(vc)
        new_cache["v_scales"] = cache["v_scales"].at[:, :, :Sd].set(vsc)
        ckc, cksc = _fp8_token_kv(cks)
        cvc, cvsc = _fp8_token_kv(cvs)
        new_cache["cross_k"], new_cache["cross_k_scales"] = ckc, cksc
        new_cache["cross_v"], new_cache["cross_v_scales"] = cvc, cvsc
    else:
        new_cache["cross_k"] = cks.astype(cache["cross_k"].dtype)
        new_cache["cross_v"] = cvs.astype(cache["cross_v"].dtype)
        new_cache["k"] = cache["k"].at[:, :, :Sd].set(ks.astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[:, :, :Sd].set(vs.astype(cache["v"].dtype))
    pos = jnp.where(positions < lens[:, None], positions, -1)
    new_cache["pos"] = cache["pos"].at[:, :Sd].set(pos)
    new_cache["len"] = lens
    new_cache["cross_len"] = jnp.full((B,), Se, jnp.int32)
    return new_cache, logits


def _enc_positions(cache, B: int, Se: int):
    """Cross-attention key positions, -1 beyond each slot's source."""
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    cross_len = cache.get("cross_len")
    if cross_len is None:
        return enc_pos
    return jnp.where(enc_pos < cross_len[:, None], enc_pos, -1)


def _dec_ffn(ctx, cfg, lp, x):
    """One decode step's norm3 and FFN (dropless experts if MoE)."""
    h = rms_norm(x, lp["norm3_scale"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _ = moe_mod.moe_apply(ctx, lp["moe"], h, top_k=cfg.moe.top_k,
                                 capacity_factor=cfg.moe.capacity_factor,
                                 act=cfg.mlp_act,
                                 parallel_mode=cfg.moe.parallel_mode,
                                 dropless=True,
                                 dispatch_groups=cfg.moe.dispatch_groups)
        return y
    return mlp(ctx, lp["mlp"], h, cfg.mlp_act, site="dec.ffn")


def encdec_decode_step(ctx: Ctx, params, cfg, tokens, cache):
    """One decoder token against self + cross caches. tokens (B,1).

    A cache carrying ``block_tables`` routes to the block-paged step.
    Like ``lm_decode_step``, a dense cache may carry an optional
    ``active`` (B,) i32 mask (injected by the engine's horizon-fused
    scan): inactive slots decode into masked positions (``pos`` stays
    -1) and their ``len`` freezes."""
    if "block_tables" in cache:
        return encdec_paged_decode_step(ctx, params, cfg, tokens, cache)
    B = tokens.shape[0]
    positions = cache["len"][:, None]
    with jax.named_scope("embed"):
        x = embed_lookup(params["embedding"], tokens, ctx.compute_dtype)
    quant = "k_codes" in cache
    fp8 = "k_scales" in cache and not quant
    scaled = quant or fp8
    Se = (cache["cross_k_codes"] if quant else cache["cross_k"]).shape[2]
    enc_pos = _enc_positions(cache, B, Se)

    if quant:
        xs = (params["decoder"]["layers"], cache["k_codes"], cache["k_scales"],
              cache["v_codes"], cache["v_scales"], cache["cross_k_codes"],
              cache["cross_k_scales"], cache["cross_v_codes"],
              cache["cross_v_scales"])
    elif fp8:
        xs = (params["decoder"]["layers"], cache["k"], cache["k_scales"],
              cache["v"], cache["v_scales"], cache["cross_k"],
              cache["cross_k_scales"], cache["cross_v"],
              cache["cross_v_scales"])
    else:
        xs = (params["decoder"]["layers"], cache["k"], cache["v"],
              cache["cross_k"], cache["cross_v"])

    def body(x, layer_xs):
        if scaled:
            lp, kc, ksc, vc, vsc, ckc, cksc, cvc, cvsc = layer_xs
        else:
            lp, kc, vc, ck, cv = layer_xs
        with jax.named_scope("dec.attn"):
            if scaled:
                k_dense, v_dense = _dense_kv(kc, ksc), _dense_kv(vc, vsc)
            else:
                k_dense, v_dense = kc, vc
            h = rms_norm(x, lp["norm1_scale"], cfg.norm_eps)
            y, k_new, v_new = decode_attn_apply(
                ctx, lp["attn"], h, positions, k_dense, v_dense,
                cache["pos"], num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                window=0, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                site="dec.attn")
            x = x + y
            if scaled:
                qfn = _quantize_token_kv if quant else _fp8_token_kv
                nkc, nks = qfn(k_new)
                nvc, nvs = qfn(v_new)
                new_kv = (_scatter_tokens(kc, nkc, cache["len"]),
                          _scatter_tokens(ksc, nks, cache["len"]),
                          _scatter_tokens(vc, nvc, cache["len"]),
                          _scatter_tokens(vsc, nvs, cache["len"]))
            else:
                new_kv = (_scatter_tokens(kc, k_new, cache["len"]),
                          _scatter_tokens(vc, v_new, cache["len"]))
        with jax.named_scope("dec.cross"):
            if scaled:
                ck, cv = _dense_kv(ckc, cksc), _dense_kv(cvc, cvsc)
            h = rms_norm(x, lp["norm2_scale"], cfg.norm_eps)
            y, _ = attn_apply(ctx, lp["cross"], h, positions,
                              num_heads=cfg.num_heads,
                              num_kv_heads=cfg.num_kv_heads,
                              head_dim=cfg.head_dim, causal=False, window=0,
                              kv_override=(ck, cv, enc_pos), use_rope=False,
                              norm_eps=cfg.norm_eps, site="dec.cross")
            x = x + y
        with jax.named_scope("dec.ffn"):
            x = x + _dec_ffn(ctx, cfg, lp, x)
        return x, new_kv

    with jax.named_scope("dec.layers"):
        x, new_kv = jax.lax.scan(body, x, xs)
    logits = _head(ctx, params, cfg, x)
    new_cache = dict(cache)
    if quant:
        (new_cache["k_codes"], new_cache["k_scales"],
         new_cache["v_codes"], new_cache["v_scales"]) = new_kv
    elif fp8:
        (new_cache["k"], new_cache["k_scales"],
         new_cache["v"], new_cache["v_scales"]) = new_kv
    else:
        new_cache["k"], new_cache["v"] = new_kv
    return _commit_decode_position(new_cache, cache, positions), logits


def encdec_init_paged_cache(cfg, slots: int, max_pages: int, num_pages: int,
                            page_size: int, kv_dtype: str = "bf16",
                            enc_len: int = 0):
    """Paged enc-dec serving cache.

    The decoder's self-attention KV is block-paged (shared pool); the
    cross-attention cache stays per-slot dense at ``enc_len`` capacity —
    it is written once per request and never grows, so paging buys
    nothing there — with per-slot ``cross_len`` masking so mixed source
    lengths coexist.
    """
    from ..serving.paged_cache import TRASH_PAGE, init_paged_kv
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    enc_len = enc_len or cfg.enc_len
    cache = init_paged_kv(L, num_pages, page_size, Hkv, hd, kv_dtype)
    if kv_dtype == "int8":
        cache.update(
            cross_k_codes=jnp.zeros((L, slots, enc_len, Hkv, hd), jnp.int8),
            cross_k_scales=jnp.zeros((L, slots, enc_len, Hkv), jnp.float32),
            cross_v_codes=jnp.zeros((L, slots, enc_len, Hkv, hd), jnp.int8),
            cross_v_scales=jnp.zeros((L, slots, enc_len, Hkv), jnp.float32))
    elif kv_dtype == "fp8":
        f8 = jnp.float8_e4m3fn
        cache.update(
            cross_k=jnp.zeros((L, slots, enc_len, Hkv, hd), f8),
            cross_k_scales=jnp.zeros((L, slots, enc_len, Hkv), jnp.float32),
            cross_v=jnp.zeros((L, slots, enc_len, Hkv, hd), f8),
            cross_v_scales=jnp.zeros((L, slots, enc_len, Hkv), jnp.float32))
    else:
        dt = jnp.float32 if kv_dtype == "f32" else jnp.bfloat16
        cache.update(
            cross_k=jnp.zeros((L, slots, enc_len, Hkv, hd), dt),
            cross_v=jnp.zeros((L, slots, enc_len, Hkv, hd), dt))
    cache["cross_len"] = jnp.zeros((slots,), jnp.int32)
    cache["block_tables"] = jnp.full((slots, max_pages), TRASH_PAGE,
                                     jnp.int32)
    cache["len"] = jnp.zeros((slots,), jnp.int32)
    cache["active"] = jnp.zeros((slots,), jnp.int32)
    return cache


def encdec_paged_decode_step(ctx: Ctx, params, cfg, tokens, cache):
    """One decoder token: paged self-attention + per-slot dense cross."""
    tables, active = cache["block_tables"], cache["active"]
    B = tokens.shape[0]
    positions = cache["len"][:, None]
    with jax.named_scope("dec.attn"):
        view_pos, pid, off = paged_view(cache)
        lengths_now = jnp.where(active > 0, cache["len"] + 1, 0)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embedding"], tokens, ctx.compute_dtype)
    quant = "k_codes" in cache
    fp8 = "k_scales" in cache and not quant
    scaled = quant or fp8
    Se = (cache["cross_k_codes"] if quant else cache["cross_k"]).shape[2]
    with jax.named_scope("dec.cross"):
        enc_pos = _enc_positions(cache, B, Se)
    use_kernel = ctx.paged_attn_impl == "kernel"

    if quant:
        xs = (params["decoder"]["layers"], cache["k_codes"],
              cache["k_scales"], cache["v_codes"], cache["v_scales"],
              cache["cross_k_codes"], cache["cross_k_scales"],
              cache["cross_v_codes"], cache["cross_v_scales"])
    elif fp8:
        xs = (params["decoder"]["layers"], cache["k"], cache["k_scales"],
              cache["v"], cache["v_scales"], cache["cross_k"],
              cache["cross_k_scales"], cache["cross_v"],
              cache["cross_v_scales"])
    else:
        xs = (params["decoder"]["layers"], cache["k"], cache["v"],
              cache["cross_k"], cache["cross_v"])

    def body(x, layer_xs):
        if scaled:
            lp, *leaves = layer_xs[:5]
            ckc, cksc, cvc, cvsc = layer_xs[5:]
        else:
            lp, *leaves = layer_xs[:3]
            ck, cv = layer_xs[3:]
        with jax.named_scope("dec.attn"):
            h = rms_norm(x, lp["norm1_scale"], cfg.norm_eps)
            y, new_leaves = paged_attn(
                ctx, lp["attn"], h, positions, leaves, view_pos, pid, off,
                lengths_now, tables, use_kernel=use_kernel,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, window=0, rope_theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps, site="dec.attn")
            x = x + y
        with jax.named_scope("dec.cross"):
            if scaled:
                ck, cv = _dense_kv(ckc, cksc), _dense_kv(cvc, cvsc)
            h = rms_norm(x, lp["norm2_scale"], cfg.norm_eps)
            y, _ = attn_apply(ctx, lp["cross"], h, positions,
                              num_heads=cfg.num_heads,
                              num_kv_heads=cfg.num_kv_heads,
                              head_dim=cfg.head_dim, causal=False, window=0,
                              kv_override=(ck, cv, enc_pos), use_rope=False,
                              norm_eps=cfg.norm_eps, site="dec.cross")
            x = x + y
        with jax.named_scope("dec.ffn"):
            x = x + _dec_ffn(ctx, cfg, lp, x)
        return x, new_leaves

    with jax.named_scope("dec.layers"):
        x, new_kv = jax.lax.scan(body, x, xs)
    logits = _head(ctx, params, cfg, x)
    new_cache = dict(cache)
    if quant:
        (new_cache["k_codes"], new_cache["k_scales"],
         new_cache["v_codes"], new_cache["v_scales"]) = new_kv
    elif fp8:
        (new_cache["k"], new_cache["k_scales"],
         new_cache["v"], new_cache["v_scales"]) = new_kv
    else:
        new_cache["k"], new_cache["v"] = new_kv
    new_cache["len"] = jnp.where(active > 0, cache["len"] + 1, cache["len"])
    return new_cache, logits
