"""Plain decoder-only transformer (StableLM-style): pre-norm blocks of causal
multi-head attention with partial rotary embeddings (NeoX rotate-half on the
leading ``rotary_pct`` of each head) and a SwiGLU MLP, in float32.

Full causal softmax attention over the whole sequence, no cache, no kernels.
The parameters of one layer are made from the seed on their own, so the
forward runs layer by layer and never holds more than one layer in float32.

``m`` is the model's entry of a configuration file (its sizes).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C
from .numerics import dot


def _keys(key, m):
    k_embed, k_layers, _, k_head = jax.random.split(key, 4)
    return k_embed, jax.random.split(k_layers, m["num_layers"]), k_head


def outer_init(key, m):
    """Embedding, final norm and output head, from the model's key."""
    k_embed, _, k_head = _keys(key, m)
    d, V = m["d_model"], C.padded_vocab(m)
    return {"embed": C.trunc_normal(k_embed, (V, d), 1.0 / math.sqrt(d),
                                    m["dtype"]),
            "final_norm": C.norm_init(d),
            "unembed": C.linear_init(k_head, d, V, m["dtype"])}


def layer_init(layer_key, m):
    d, H, hd, ff = m["d_model"], m["num_heads"], m["head_dim"], m["d_ff"]
    KV, dtype = m["num_kv_heads"], m["dtype"]
    k_attn, k_mlp = jax.random.split(layer_key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    k1, k2, k3 = jax.random.split(k_mlp, 3)
    return {
        "norm1": C.norm_init(d), "norm2": C.norm_init(d),
        "wq": C.linear_init(kq, d, H * hd, dtype),
        "wk": C.linear_init(kk, d, KV * hd, dtype),
        "wv": C.linear_init(kv, d, KV * hd, dtype),
        "wo": C.linear_init(ko, H * hd, d, dtype,
                            std=1.0 / math.sqrt(H * hd * 2 * m["num_layers"])),
        "w_in": C.linear_init(k1, d, ff, dtype),
        "w_out": C.linear_init(k2, ff, d, dtype),
        "w_gate": C.linear_init(k3, d, ff, dtype),
    }


def _rope(x, m):
    """Rotate-half on the leading rot channels; x (B, S, H, hd)."""
    hd = x.shape[-1]
    rot = int(m["rotary_pct"] * hd)
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    inv = 1.0 / (m["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                     / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def layer(p, x, m, prec="f32"):
    """One block over the whole sequence: x (B, S, d) float32."""
    B, S, d = x.shape
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    h = C.rmsnorm(p["norm1"], x, m["norm_eps"])
    q = _rope(dot(h, p["wq"]["w"], prec).reshape(B, S, H, hd), m)
    k = _rope(dot(h, p["wk"]["w"], prec).reshape(B, S, KV, hd), m)
    v = dot(h, p["wv"]["w"], prec).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + dot(a.reshape(B, S, H * hd), p["wo"]["w"], prec)
    h = C.rmsnorm(p["norm2"], x, m["norm_eps"])
    g = jax.nn.silu(dot(h, p["w_gate"]["w"], prec)) * dot(h, p["w_in"]["w"],
                                                           prec)
    return x + dot(g, p["w_out"]["w"], prec)


def logits(key, tokens, m, prec="f32", layer_fn=None, tail=None):
    """tokens (B, S) -> logits (B, S, padded vocab) of the last ``tail``
    positions (all by default), one layer at a time: each layer's weights
    are made from the model's key, used and dropped."""
    _, layer_keys, _ = _keys(key, m)
    o = outer_init(key, m)
    x = o["embed"][tokens]
    run = layer_fn or jax.jit(
        lambda key, x: layer(layer_init(key, m), x, m, prec))
    for i in range(m["num_layers"]):
        x = run(layer_keys[i], x)
    if tail is not None:
        x = x[:, -tail:]
    x = C.rmsnorm(o["final_norm"], x, m["norm_eps"])
    return dot(x, o["unembed"]["w"], prec)
