"""Serving-step factories: prefill (prompt -> last-token logits + caches) and
decode (one token against caches), plus greedy/temperature sampling."""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch) -> Tuple[jnp.ndarray, Any]:
        logits, _, cache = M.forward(params, cfg, batch, mode="prefill")
        return logits, cache
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, batch, cache) -> Tuple[jnp.ndarray, Any]:
        return M.decode(params, cfg, batch, cache)
    return decode_step


def sample(logits: jnp.ndarray, key, temperature: float = 0.0,
           vocab_size: int = 0) -> jnp.ndarray:
    """logits (B,1,V) -> tokens (B,1). temperature 0 = greedy.
    Padded-vocab tail is masked out."""
    if vocab_size:
        neg = jnp.full_like(logits, -1e30)
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, neg)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


# attention cache leaves of decode, and how far from the end their sequence
# axis lies: last for GQA k/v (..., B, KV, hd, S), second from the end for
# MLA c_kv/k_rope (..., B, S, r)
KV_SEQ_FROM_END = {"k": 1, "v": 1, "c_kv": 2, "k_rope": 2}


def pad_cache(cache: Dict[str, Any], cfg: ModelConfig, max_len: int
              ) -> Dict[str, Any]:
    """Prefill-sized caches (seq dim == prompt len) to decode's, ``max_len``
    long so decode can append. Prefill's GQA k/v (..., B, S, KV, hd) turn
    into decode's (..., B, KV, hd, S) here, once per task, in the same pass
    as the pad; MLA's c_kv/k_rope (..., B, S, r) are only padded. Leaves may
    be stacked over layers or held one per shared-block invocation
    (hybrid)."""
    def grow(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
        if name not in KV_SEQ_FROM_END:
            return leaf
        return _to_decode(leaf, max_len, KV_SEQ_FROM_END[name])
    return jax.tree_util.tree_map_with_path(grow, cache)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _to_decode(leaf, max_len: int, seq_from_end: int):
    if seq_from_end == 1:                   # GQA: (S, KV, hd) -> (KV, hd, S)
        leaf = jnp.moveaxis(leaf, -3, -1)
    seq_ax = leaf.ndim - seq_from_end
    pad = [(0, 0)] * leaf.ndim
    pad[seq_ax] = (0, max(0, max_len - leaf.shape[seq_ax]))
    return jnp.pad(leaf, pad)
