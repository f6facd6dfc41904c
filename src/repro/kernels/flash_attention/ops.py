"""Jit-facing wrapper: model layout (B, S, H, hd) in/out, Pallas kernel or
jnp fallback, CPU-interpret switch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from ..nograd import forward_only
from .flash_attention import flash_attention_bhsd


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "use_pallas",
                                    "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    use_pallas: bool = True, interpret: bool = False):
    """q (B, S, H, hd), k/v (B, S, KV, hd) -> (B, S, H, hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if use_pallas:
        ot = forward_only(
            "flash_attention",
            lambda q, k, v: flash_attention_bhsd(q, k, v, scale=scale,
                                                 causal=causal,
                                                 interpret=interpret),
            qt, kt, vt)
    else:
        ot = ref.attention_ref(qt, kt, vt, scale=scale, causal=causal)
    return jnp.swapaxes(ot, 1, 2)
