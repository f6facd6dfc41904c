"""Plain Zamba2 (arXiv 2411.15242; HF transformers ``modeling_zamba2.py``)
in float32: a Mamba2 backbone in which the j-th layer of
``hybrid_layer_ids`` first runs shared block j mod ``num_mem_blocks``
(attention and a GELU MLP over concat(h, x0), x0 the token embedding)
with the invocation's own LoRA on the MLP's gate/up and its own linear
after the block, and adds the result to that layer's mixer input:

    t = Lin_j(MLP_b,j(RMSNorm(Attn_b(RMSNorm(concat(h, x0))))))
    h = h + Mamba2_l(RMSNorm_l(h + t))

Attention is full causal softmax over the sequence with scores scaled by
(head_dim / 2)^-1/2 and rotate-half RoPE over the whole head; Mamba2 is
the quadratic dual form of ``mamba2.py`` with biased convolutions and its
gated norm taken per B/C group. No cache, no chunking, no kernels.

Each layer's weights are made from the seed on their own, by the recipe the
system documents (``common.py``), used and dropped, so the forward never
holds more than one layer in float32. ``m`` is the model's entry of a
configuration file (its sizes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common as C
from .mamba2 import _causal_conv, _layer_init as _mamba_init, _ssd_row, dims
from .numerics import dot, through

HIGHEST = jax.lax.Precision.HIGHEST


def keys(key, m):
    """Keys of the embedding, of each mamba2 layer, of each shared block
    and of each invocation, as the system splits the model's key."""
    k_embed, k_layers, k_extra, _ = jax.random.split(key, 4)
    return (k_embed, jax.random.split(k_layers, m["num_layers"]),
            jax.random.split(k_extra, m["num_mem_blocks"]),
            jax.random.split(jax.random.fold_in(k_extra, 1),
                             len(m["hybrid_layer_ids"])))


def embed_init(key, m):
    d = m["d_model"]
    return C.trunc_normal(key, (C.padded_vocab(m), d), 1.0 / math.sqrt(d),
                          m["dtype"])


def mamba_init(key, m):
    p = _mamba_init(key, m)
    d, di, H, P, G, N, K = dims(m)
    lim = 1.0 / math.sqrt(K)
    for i, (name, c) in enumerate((("conv_x", di), ("conv_B", G * N),
                                   ("conv_C", G * N))):
        p["ssm"][name + "_bias"] = through(jax.random.uniform(
            jax.random.fold_in(key, 100 + i), (c,), minval=-lim,
            maxval=lim), jnp.dtype(m["dtype"]))
    return p


def shared_init(key, m):
    d, H, KV, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    dtype = m["dtype"]
    k_attn, k_mlp = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    k1, k2, k3 = jax.random.split(k_mlp, 3)
    return {
        "norm_in": C.norm_init(2 * d), "norm_ff": C.norm_init(d),
        "wq": C.linear_init(kq, 2 * d, H * hd, dtype),
        "wk": C.linear_init(kk, 2 * d, KV * hd, dtype),
        "wv": C.linear_init(kv, 2 * d, KV * hd, dtype),
        "wo": C.linear_init(ko, H * hd, d, dtype,
                            std=1.0 / math.sqrt(H * hd * 2 * m["num_layers"])),
        "w_in": C.linear_init(k1, d, ff, dtype),
        "w_out": C.linear_init(k2, ff, d, dtype),
        "w_gate": C.linear_init(k3, d, ff, dtype),
    }


def invocation_init(key, m):
    d, r, ff = m["d_model"], m["adapter_rank"], m["d_ff"]
    ka, kb, kl = jax.random.split(key, 3)
    return {"lora_a": C.linear_init(ka, d, r, m["dtype"]),
            "lora_b": C.linear_init(kb, r, 2 * ff, m["dtype"]),
            "linear": C.linear_init(kl, d, d, m["dtype"])}


def group_rmsnorm(p, x, eps, groups):
    """RMSNorm of each of ``groups`` equal slices of the last axis."""
    xg = x.reshape(x.shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
    return (xg * jax.lax.rsqrt(var + eps)).reshape(x.shape) * (
        1.0 + p["scale"])


def mixer(p, h, m, prec):
    d, di, H, P, G, N, K = dims(m)
    B, S, _ = h.shape

    def conv(name, w):
        return jax.nn.silu(_causal_conv(dot(h, p[w]["w"], prec), p[name])
                           + p[name + "_bias"])

    z = dot(h, p["wz"]["w"], prec)
    xi, Bi, Ci = conv("conv_x", "wx"), conv("conv_B", "wB"), conv("conv_C",
                                                                  "wC")
    dt = jax.nn.softplus(dot(h, p["wdt"]["w"], prec) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    x4 = xi.reshape(B, S, H, P)
    y = jax.lax.map(lambda r: _ssd_row(r[0], r[1], A, r[2], r[3]),
                    (x4, dt, Bi.reshape(B, S, G, N), Ci.reshape(B, S, G, N)))
    y = y + p["D"][None, None, :, None] * x4
    y = group_rmsnorm(p["norm"], y.reshape(B, S, di) * jax.nn.silu(z),
                      m["norm_eps"], G)
    return dot(y, p["w_out"]["w"], prec)


def mamba_layer(p, x, t, m, prec):
    """x + Mamba2(RMSNorm(x + t)); t is zero where no block feeds it."""
    return x + mixer(p["ssm"], C.rmsnorm(p["norm1"], x + t, m["norm_eps"]),
                     m, prec)


def _rope(x, theta):
    """Rotate-half over the whole head; x (B, S, H, hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def shared_block(p, inv, h, x0, m, prec):
    """One invocation: its output t (no residual of its own)."""
    B, S, d = h.shape
    H, KV, hd, ff = m["num_heads"], m["num_kv_heads"], m["head_dim"], \
        m["d_ff"]
    a = C.rmsnorm(p["norm_in"], jnp.concatenate([h, x0], -1), m["norm_eps"])
    q = _rope(dot(a, p["wq"]["w"], prec).reshape(B, S, H, hd),
              m["rope_theta"])
    k = _rope(dot(a, p["wk"]["w"], prec).reshape(B, S, KV, hd),
              m["rope_theta"])
    v = dot(a, p["wv"]["w"], prec).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * (
        (hd / 2) ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    o = dot(o.reshape(B, S, H * hd), p["wo"]["w"], prec)
    mm = C.rmsnorm(p["norm_ff"], o, m["norm_eps"])
    gu = dot(mm, jnp.concatenate([p["w_gate"]["w"], p["w_in"]["w"]], -1),
             prec) + dot(dot(mm, inv["lora_a"]["w"], prec),
                         inv["lora_b"]["w"], prec)
    g, u = gu[..., :ff], gu[..., ff:]
    t = dot(jax.nn.gelu(g, approximate=False) * u, p["w_out"]["w"], prec)
    return dot(t, inv["linear"]["w"], prec)


def _hashable(m):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


@functools.lru_cache(maxsize=None)
def _steps(m_items, prec):
    m = dict(m_items)
    layer = jax.jit(lambda key, x, t: mamba_layer(mamba_init(key, m), x, t,
                                                  m, prec))
    block = jax.jit(lambda kb, ki, h, x0: shared_block(
        shared_init(kb, m), invocation_init(ki, m), h, x0, m, prec))
    return layer, block


def logits(key, tokens, m, prec="f32", tail=None):
    """tokens (B, S) -> logits (B, S, padded vocab) of the last ``tail``
    positions (all by default), float32, one layer at a time."""
    layer, block = _steps(_hashable(m), prec)
    k_embed, k_layers, k_blocks, k_inv = keys(key, m)
    table = embed_init(k_embed, m)
    x0 = table[tokens]
    h = x0
    ids = list(m["hybrid_layer_ids"])
    for i in range(m["num_layers"]):
        t = jnp.zeros_like(h)
        if i in ids:
            j = ids.index(i)
            t = block(k_blocks[j % m["num_mem_blocks"]], k_inv[j], h, x0)
        h = layer(k_layers[i], h, t)
    if tail is not None:
        h = h[:, -tail:]
    h = C.rmsnorm(C.norm_init(m["d_model"]), h, m["norm_eps"])
    return dot(h, table.T, prec)
