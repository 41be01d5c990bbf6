"""GQA attention with RoPE, qk-norm, optional bias, KV-cache decode.

The default implementation is pure XLA (jnp einsums) so that
compilation on any backend succeeds; the Pallas flash kernel
(`repro.kernels.flash_attention`) is swapped in via cfg.attention_impl="flash"
on real TPU hardware.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import apply_rope, dense_init, rms_norm


def init_attention(key, cfg, d_model=None):
    D = d_model or cfg.d_model
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd),
        "wk": dense_init(ks[1], D, KV * hd),
        "wv": dense_init(ks[2], D, KV * hd),
        "wo": dense_init(ks[3], H * hd, D),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _project_qkv(x, p, cfg, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, H, hd)
    k = (x @ p["wk"].astype(dt)).reshape(B, S, KV, hd)
    v = (x @ p["wv"].astype(dt)).reshape(B, S, KV, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt).reshape(H, hd)
        k = k + p["bk"].astype(dt).reshape(KV, hd)
        v = v + p["bv"].astype(dt).reshape(KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is not None:  # rope (None => learned/absolute handled by caller)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg):
    """q: (B,Sq,H,hd)  k/v: (B,Sk,KV,hd). GQA via head grouping."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    if cfg.logits_softcap:
        scores = cfg.logits_softcap * jnp.tanh(scores / cfg.logits_softcap)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def causal_mask(Sq: int, Sk: int, offset: int = 0):
    """mask[i, j] = query (offset+i) may attend key j."""
    qi = jnp.arange(Sq)[:, None] + offset
    kj = jnp.arange(Sk)[None, :]
    return (kj <= qi)[None, None, None, :, :]  # (1,1,1,Sq,Sk) for bkgqs scores


def _sdpa_chunked(q, k, v, cfg, *, causal: bool = True, offset: int = 0):
    """Blocked attention: lax.scan over q-row blocks, scores for one block at
    a time — peak scores memory (B,KV,G,bq,Sk) instead of (...,Sq,Sk). The
    XLA stand-in for the Pallas flash kernel at 32k+ sequence.

    q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(cfg.attn_q_block, Sq)
    nb = Sq // bq
    rem = Sq - nb * bq
    scale = hd ** -0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def block(qb, qstart):
        """qb: (B,bq',KV,G,hd) -> (B,bq',H,hd)"""
        s = jnp.einsum("bqkgh,bskh->bkgqs", qb.astype(jnp.float32), kf) * scale
        if cfg.logits_softcap:
            s = cfg.logits_softcap * jnp.tanh(s / cfg.logits_softcap)
        if causal:
            rows = offset + qstart + jnp.arange(qb.shape[1])[:, None]
            cols = jnp.arange(Sk)[None, :]
            s = jnp.where((cols <= rows)[None, None, None],
                          s, jnp.finfo(jnp.float32).min)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskh->bqkgh", w, vf).astype(q.dtype)

    qg = q.reshape(B, Sq, KV, G, hd)
    main = qg[:, : nb * bq].reshape(B, nb, bq, KV, G, hd)

    # remat per block: without it the scan saves every block's (bq, Sk)
    # softmax weights for backward — O(Sq*Sk) again, defeating the blocking.
    block = jax.checkpoint(block, static_argnums=())

    def body(_, xs):
        qb, i = xs
        return None, block(qb, i * bq)

    _, out = jax.lax.scan(body, None,
                          (jnp.moveaxis(main, 1, 0), jnp.arange(nb)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * bq, H, hd)
    if rem:
        tail = block(qg[:, nb * bq:], nb * bq)
        out = jnp.concatenate([out, tail.reshape(B, rem, H, hd)], axis=1)
    return out


def attention(x, p, cfg, positions=None, mask=None, kv_cache=None, cache_pos=None,
              kv_override=None):
    """Full attention block body (no residual / norm).

    kv_cache: optional dict {"k": (B,Smax,KV,hd), "v": ...} — decode mode:
      new k/v written at cache_pos, attention over the whole cache.
    kv_override: (k, v) precomputed — cross-attention (whisper decoder).
    Returns (out, new_cache).
    """
    dt = x.dtype

    def use_chunked(Sq):
        return (cfg.attention_impl == "chunked"
                and Sq >= 2 * cfg.attn_q_block)

    if kv_override is not None:
        B, Sq = x.shape[:2]
        H, hd = cfg.num_heads, cfg.head_dim
        q = (x @ p["wq"].astype(dt)).reshape(B, Sq, H, hd)
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dt).reshape(H, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k, v = kv_override
        if use_chunked(Sq) and mask is None:
            out = _sdpa_chunked(q, k, v, cfg, causal=False)
        else:
            out = _sdpa(q, k, v, mask, cfg)
        return (out.reshape(B, Sq, H * hd) @ p["wo"].astype(dt)), None

    q, k, v = _project_qkv(x, p, cfg, positions)
    new_cache = None
    if kv_cache is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k.astype(kv_cache["k"].dtype), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v.astype(kv_cache["v"].dtype), cache_pos, axis=1)
        new_cache = {"k": ck, "v": cv}
        Smax = ck.shape[1]
        if use_chunked(q.shape[1]) and mask is None and cfg.causal:
            out = _sdpa_chunked(q, ck.astype(dt), cv.astype(dt), cfg,
                                causal=True, offset=cache_pos)
        else:
            if mask is None:
                mask = causal_mask(q.shape[1], Smax, offset=cache_pos)
            out = _sdpa(q, ck.astype(dt), cv.astype(dt), mask, cfg)
    else:
        if use_chunked(q.shape[1]) and mask is None and cfg.causal:
            out = _sdpa_chunked(q, k, v, cfg, causal=True)
        else:
            if mask is None and cfg.causal:
                mask = causal_mask(q.shape[1], k.shape[1])
            out = _sdpa(q, k, v, mask, cfg)
    B, Sq = x.shape[:2]
    out = out.reshape(B, Sq, cfg.num_heads * cfg.head_dim) @ p["wo"].astype(dt)
    return out, new_cache


def cross_kv(enc, p, cfg):
    """Precompute cross-attention K/V from encoder output (whisper)."""
    B, S, _ = enc.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    dt = enc.dtype
    k = (enc @ p["wk"].astype(dt)).reshape(B, S, KV, hd)
    v = (enc @ p["wv"].astype(dt)).reshape(B, S, KV, hd)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt).reshape(KV, hd)
        v = v + p["bv"].astype(dt).reshape(KV, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v
