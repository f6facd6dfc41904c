"""Attention variants: GQA/MQA/MHA with RoPE flavors, and DeepSeek MLA.

Three entry modes, all pure functions:
  * full-sequence causal (train / prefill)
  * single-token decode against a KV cache
  * MLA decode uses the *absorbed-weight* formulation (scores computed in the
    512-dim latent space; only (c_kv, k_rope) are cached — the MLA memory win).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_rope, init_linear, linear, init_rmsnorm,
                                 rmsnorm, rope_cos_sin, rot_dim_for)

NEG_INF = -2.0e38


# ============================================================ core (XLA path)
def attn_weights_core(q, k, *, scale: float) -> jnp.ndarray:
    """Grouped-query causal attention scores+softmax, q and k both from
    position 0.

    q: (B, Sq, KV, G, hd); k: (B, Sk, KV, hd). Returns weights (B,KV,G,Sq,Sk) f32.
    """
    Sq, Sk = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]    # causal
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    return jax.nn.softmax(scores, axis=-1)


def attn_core(q, k, v, *, scale: float, use_pallas: bool = False
              ) -> jnp.ndarray:
    """Full causal sequence from position 0: q (B,Sq,H,hd), k/v (B,Sk,KV,hd)
    -> (B,Sq,H,hd_v).

    ``use_pallas=True`` runs the flash kernel; a value width other than the
    query's (MLA) raises rather than silently running the jnp path. Decode
    against a cache is ``gqa_decode``'s own (the decode attention kernel)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if use_pallas:
        if v.shape[-1] != hd:
            raise ValueError(
                f"use_pallas: no Pallas attention kernel for value width "
                f"{v.shape[-1]} != query width {hd} (MLA); use_pallas=False")
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, scale=scale, causal=True)
    qg = q.reshape(B, Sq, KV, G, hd)
    w = attn_weights_core(qg, k, scale=scale)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# ================================================================== GQA layer
def init_gqa(key, cfg: ModelConfig, dtype, d_in: Optional[int] = None):
    """Q/K/V read ``d_in`` channels (d_model unless given: zamba2's shared
    blocks read concat(h, x0), 2 d_model); the output is d_model wide."""
    H, KV, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    d_in = d_in or d
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_linear(kq, d_in, H * hd, dtype, bias=cfg.qkv_bias),
        "wk": init_linear(kk, d_in, KV * hd, dtype, bias=cfg.qkv_bias),
        "wv": init_linear(kv, d_in, KV * hd, dtype, bias=cfg.qkv_bias),
        "wo": init_linear(ko, H * hd, d, dtype,
                          stddev=1.0 / math.sqrt(H * hd * 2 * cfg.num_layers)),
    }


def gqa_rope(cfg: ModelConfig, q, k, positions):
    rd = rot_dim_for(cfg, cfg.head_dim)
    if rd == 0 or positions is None:
        return q, k
    cos, sin = rope_cos_sin(cfg, positions, rd)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


def gqa_full(p, x, cfg: ModelConfig, positions, *, return_kv: bool = False,
             scale: Optional[float] = None):
    """Full-sequence causal attention (train / prefill); scores scaled by
    ``scale``, 1/sqrt(head_dim) unless given.

    Returns (out, (k, v) or None). positions: (B,S) or (3,B,S) for mrope.
    """
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, H, hd)
    k = linear(p["wk"], x).reshape(B, S, KV, hd)
    v = linear(p["wv"], x).reshape(B, S, KV, hd)
    q, k = gqa_rope(cfg, q, k, positions)
    o = attn_core(q, k, v, scale=scale or 1.0 / math.sqrt(hd),
                  use_pallas=cfg.use_pallas)
    out = linear(p["wo"], o.reshape(B, S, H * hd))
    return out, ((k, v) if return_kv else None)


def gqa_decode(p, x, cfg: ModelConfig, positions, k_cache, v_cache, index,
               scale: Optional[float] = None):
    """Single-token decode. x (B,1,d_in); caches (B,KV,hd,Smax), sequence
    last, as the decode attention kernel reads them in place; index =
    #tokens already cached. The new (B,KV,hd,1) column is written with one
    ``dynamic_update_slice`` on the last axis, in place where the caches are
    donated. Returns (out, new_k_cache, new_v_cache)."""
    from repro.kernels.decode_attention import ops as da_ops
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(B, 1, H, hd)
    k = linear(p["wk"], x).reshape(B, 1, KV, hd)
    v = linear(p["wv"], x).reshape(B, 1, KV, hd)
    q, k = gqa_rope(cfg, q, k, positions)

    def put(cache, new):                    # (B,1,KV,hd) -> column at index
        col = jnp.transpose(new, (0, 2, 3, 1)).astype(cache.dtype)
        return jax.lax.dynamic_update_slice_in_dim(cache, col, index, axis=3)

    k_cache, v_cache = put(k_cache, k), put(v_cache, v)
    o = da_ops.decode_attention(q, k_cache, v_cache, index + 1,
                                scale=scale or 1.0 / math.sqrt(hd),
                                use_pallas=cfg.use_pallas)
    out = linear(p["wo"], o.reshape(B, 1, H * hd))
    return out, k_cache, v_cache


# ================================================================== MLA layer
def init_mla(key, cfg: ModelConfig, dtype):
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d, vdim, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                             cfg.v_head_dim, cfg.kv_lora_rank)
    ks = jax.random.split(key, 6)
    return {
        "wq": init_linear(ks[0], d, H * (nope + rope_d), dtype),
        "w_dkv": init_linear(ks[1], d, r, dtype),
        "w_krope": init_linear(ks[2], d, rope_d, dtype),
        "kv_norm": init_rmsnorm(r, dtype),
        "w_uk": init_linear(ks[3], r, H * nope, dtype),
        "w_uv": init_linear(ks[4], r, H * vdim, dtype),
        "wo": init_linear(ks[5], H * vdim, d, dtype,
                          stddev=1.0 / math.sqrt(H * vdim * 2 * cfg.num_layers)),
    }


def _mla_dims(cfg):
    return (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank)


def mla_latents(p, x, cfg: ModelConfig, positions):
    """Compute (c_kv, k_rope) — the quantities MLA caches."""
    B, S, _ = x.shape
    H, nope, rope_d, vdim, r = _mla_dims(cfg)
    c_kv = rmsnorm(p["kv_norm"], linear(p["w_dkv"], x), cfg.norm_eps)   # (B,S,r)
    k_rope = linear(p["w_krope"], x).reshape(B, S, 1, rope_d)
    cos, sin = rope_cos_sin(cfg, positions, rope_d)
    k_rope = apply_rope(k_rope, cos, sin)
    return c_kv, k_rope, (cos, sin)


def mla_full(p, x, cfg: ModelConfig, positions, *, return_kv: bool = False):
    """Full-sequence MLA (train / prefill). Decompresses K/V explicitly."""
    B, S, _ = x.shape
    H, nope, rope_d, vdim, r = _mla_dims(cfg)
    c_kv, k_rope, (cos, sin) = mla_latents(p, x, cfg, positions)
    q = linear(p["wq"], x).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, cos, sin)
    k_nope = linear(p["w_uk"], c_kv).reshape(B, S, H, nope)
    v = linear(p["w_uv"], c_kv).reshape(B, S, H, vdim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope_d))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    o = attn_core(qf, k, v, scale=1.0 / math.sqrt(nope + rope_d),
                  use_pallas=cfg.use_pallas)
    out = linear(p["wo"], o.reshape(B, S, H * vdim))
    return out, ((c_kv, k_rope[:, :, 0, :]) if return_kv else None)


def mla_decode(p, x, cfg: ModelConfig, positions, ckv_cache, krope_cache, index):
    """Absorbed-weight MLA decode.

    scores[h, s] = q_nope[h] @ W_uk[h]^T @ c_kv[s]  +  q_rope[h] @ k_rope[s]
    out[h]       = (sum_s w[h,s] c_kv[s]) @ W_uv[h]
    Caches: ckv_cache (B,Smax,r), krope_cache (B,Smax,rope_d).
    """
    B = x.shape[0]
    H, nope, rope_d, vdim, r = _mla_dims(cfg)
    c_kv, k_rope, (cos, sin) = mla_latents(p, x, cfg, positions)
    ckv_cache = jax.lax.dynamic_update_slice_in_dim(
        ckv_cache, c_kv.astype(ckv_cache.dtype), index, axis=1)
    krope_cache = jax.lax.dynamic_update_slice_in_dim(
        krope_cache, k_rope[:, :, 0, :].astype(krope_cache.dtype), index, axis=1)

    q = linear(p["wq"], x).reshape(B, 1, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, cos, sin)
    w_uk = p["w_uk"]["w"].reshape(r, H, nope)
    # absorb: q_lat (B,1,H,r)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = 1.0 / math.sqrt(nope + rope_d)
    s_lat = jnp.einsum("bqhr,bsr->bhqs", q_lat,
                       ckv_cache.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                        krope_cache.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    Sk = ckv_cache.shape[1]
    mask = jnp.arange(Sk)[None, None, None, :] < (index + 1)
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", w, ckv_cache.astype(jnp.float32))
    w_uv = p["w_uv"]["w"].reshape(r, H, vdim)
    o = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, w_uv.astype(jnp.float32))
    out = linear(p["wo"], o.reshape(B, 1, H * vdim).astype(x.dtype))
    return out, ckv_cache, krope_cache
