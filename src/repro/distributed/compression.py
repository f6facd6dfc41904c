"""Int8-compressed data-parallel gradient reduction.

The train step computes *local* gradients per data-parallel shard inside a
``shard_map`` that is manual over the DP mesh axes only (``axis_names=dp``;
the ``model`` axis stays on compiler auto-sharding). The cross-shard mean is
then an explicit int8 psum: 4x less ICI traffic than fp32 grads, 2x less than
bf16. Per-leaf symmetric scaling with a pmax-shared scale keeps the int32
accumulation exact; the quantization error is bounded by |g|_inf/127
(cf. 8-bit collective literature, Dettmers et al. 2022).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def quantize_int8(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                    ).astype(jnp.int8)


def int8_psum_mean(g: jnp.ndarray, axes: Tuple[str, ...], n_shards: int
                   ) -> jnp.ndarray:
    """Mean of per-shard tensors across ``axes`` with an int8 *wire* format.

    A plain ``psum(int8->int32)`` moves int32 on the wire (no win — measured
    and refuted in EXPERIMENTS.md §Perf it-3). The bandwidth-correct schedule
    is reduce-scatter + all-gather with both phases in int8:
        all_to_all(int8 chunks) -> local f32 sum -> requantize ->
        all_gather(int8)
    = 2 bytes/element on the wire vs 8 (f32 all-reduce) or 4 (bf16).
    Must be called inside a shard_map manual over ``axes``."""
    if n_shards == 1:
        scale = jnp.maximum(jnp.max(jnp.abs(g.astype(jnp.float32))),
                            1e-12) / 127.0
        return quantize_int8(g, scale).astype(jnp.float32) * scale
    assert len(axes) == 1, "compose multi-axis DP into one reduction axis"
    ax = axes[0]
    shape = g.shape
    flat = g.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % n_shards
    if pad:
        flat = jnp.pad(flat, (0, pad))
    m = flat.size // n_shards
    scale = jnp.maximum(jnp.max(jnp.abs(flat)), 1e-12) / 127.0
    scale = jax.lax.pmax(scale, ax)
    q = quantize_int8(flat, scale).reshape(n_shards, m)
    # phase 1 (int8 wire): shard i receives chunk i from every peer
    chunks = jax.lax.all_to_all(q, ax, split_axis=0, concat_axis=0,
                                tiled=False)
    part = jnp.sum(chunks.astype(jnp.float32), axis=0) * scale / n_shards
    # phase 2 (int8 wire): share the reduced chunk back to all shards
    scale2 = jnp.maximum(jnp.max(jnp.abs(part)), 1e-12) / 127.0
    scale2 = jax.lax.pmax(scale2, ax)
    q2 = quantize_int8(part, scale2)
    full = jax.lax.all_gather(q2, ax, axis=0, tiled=False)
    out = full.astype(jnp.float32).reshape(-1) * scale2
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


def make_local_grad_fn(loss_fn: Callable, mesh: Mesh,
                       dp_axes: Tuple[str, ...],
                       batch_dim_map: Dict[str, int],
                       compress: bool = True):
    """grads(params, batch) with explicit (optionally int8) DP reduction.

    ``loss_fn(params, local_batch) -> (loss, metrics)`` must compute a *mean*
    over its local batch. ``batch_dim_map`` gives the batch dim per input key
    (0 for tokens/labels, 1 for mrope positions).
    """
    n = 1
    for a in dp_axes:
        n *= mesh.shape[a]
    grad_fn = jax.grad(loss_fn, has_aux=True)

    def local_grads(params, batch):
        param_specs = jax.tree.map(lambda _: P(), params)
        batch_specs = {}
        for k, v in batch.items():
            spec = [None] * v.ndim
            spec[batch_dim_map.get(k, 0)] = dp_axes
            batch_specs[k] = P(*spec)

        @partial(jax.shard_map, mesh=mesh, axis_names=frozenset(dp_axes),
                 in_specs=(param_specs, batch_specs),
                 out_specs=(param_specs, P()), check_vma=False)
        def inner(p, b):
            g, metrics = grad_fn(p, b)
            if compress:
                g = jax.tree.map(lambda x: int8_psum_mean(x, dp_axes, n), g)
            else:
                g = jax.tree.map(
                    lambda x: jax.lax.psum(x.astype(jnp.float32), dp_axes) / n, g)
            metrics = jax.tree.map(
                lambda x: jax.lax.psum(x, dp_axes) / n, metrics)
            return g, metrics

        return inner(params, batch)

    return local_grads
