"""Pure-jnp oracle for single-token decode attention with a valid-length
masked KV cache."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k, v, valid_len, *, scale: float):
    """q (B, H, 1, hd), k/v (B, KV, hd, S) -> (B, H, 1, hd)."""
    B, H, _, hd = q.shape
    KV, S = k.shape[1], k.shape[3]
    G = H // KV
    qg = q.reshape(B, KV, G, 1, hd).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bkds->bkgqs", qg, k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, None, None, :] < valid_len
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bkds->bkgqd", w, v.astype(jnp.float32))
    return o.reshape(B, H, 1, hd).astype(q.dtype)
