"""Jit-facing wrapper: model layout (B, 1, H, hd) + cache (B, KV, hd, S), the
decode cache's own layout (sequence last, see ``decode_attention.py``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from ..nograd import forward_only
from .decode_attention import decode_attention_bhd


@functools.partial(jax.jit, static_argnames=("scale", "use_pallas",
                                             "interpret", "block_k"))
def decode_attention(q, k_cache, v_cache, valid_len, *, scale: float,
                     use_pallas: bool = True, interpret: bool = False,
                     block_k: int = 512):
    """q (B, 1, H, hd), caches (B, KV, hd, S) -> (B, 1, H, hd). The caches
    pass through as they are; only the query and output swap two axes."""
    qt = jnp.swapaxes(q, 1, 2)                    # (B, H, 1, hd)
    if use_pallas:
        ot = forward_only(
            "decode_attention",
            lambda q, k, v, n: decode_attention_bhd(q, k, v, n, scale=scale,
                                                    block_k=block_k,
                                                    interpret=interpret),
            qt, k_cache, v_cache, valid_len)
    else:
        ot = ref.decode_attention_ref(qt, k_cache, v_cache, valid_len,
                                      scale=scale)
    return jnp.swapaxes(ot, 1, 2)
