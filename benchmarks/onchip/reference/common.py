"""Pieces both plain references share: initializers, norms, embeddings.

The initializers follow the weight recipe that the system under test
documents for a seed (truncated normals at 1/sqrt(fan-in), RMSNorm scales
stored as w - 1), so that a reference made from the same seed holds the same
weights. Nothing here imports the system under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .numerics import through


def padded_vocab(m: dict) -> int:
    k = m["vocab_pad_multiple"]
    return ((m["vocab_size"] + k - 1) // k) * k


def trunc_normal(key, shape, std, dtype):
    """Draw in float32, store in ``dtype``, hand back float32."""
    w = std * jax.random.truncated_normal(key, -2.0, 2.0, shape)
    return through(w, jnp.dtype(dtype))


def linear_init(key, d_in, d_out, dtype, std=None):
    return {"w": trunc_normal(key, (d_in, d_out),
                              std if std is not None else 1.0 / math.sqrt(d_in),
                              dtype)}


def norm_init(d):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
