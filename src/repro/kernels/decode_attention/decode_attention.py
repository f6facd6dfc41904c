"""Single-token decode attention (flash-decode style) as a Pallas TPU kernel.

One query position per sequence attends to a long KV cache with a dynamic
valid length. Grid (batch, q_heads, kv_blocks): kv blocks stream through VMEM
innermost with online-softmax statistics in scratch (the TPU analogue of
split-KV: the sequential grid walks KV partitions without rematerializing
them; cache stays in HBM and is block-DMA'd). The valid cache length arrives
as a scalar-prefetch operand so out-of-range blocks are masked (and the
kernel does no work past the last valid block).

The cache is read as the chip holds it: K and V are (B, KV, hd, S), sequence
last, so each block is an (hd, block_k) tile with the sequence on the 128
lanes. That is the TPU's own layout for a cache whose head width is no
multiple of 128 (224, 80), whatever the logical axis order, so a cache kept
this way reaches the kernel with no relayout, pad or copy around the call.
The K/V index maps clamp the block index to the last valid block: blocks past
it are not fetched again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
LANES = 128


def kv_block(S: int, block_k: int) -> int:
    """The largest multiple of 128 that divides ``S`` and is at most
    ``block_k`` (at least 128), or ``S`` itself when it is no multiple of
    128: a block is then the whole sequence."""
    if S % LANES:
        return S
    n = S // LANES
    per = max(1, min(block_k // LANES, n))
    while n % per:
        per -= 1
    return per * LANES


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    valid = len_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ik * block_k < valid)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                 # (1, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (hd, bk)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, block_k), 1)
        s = jnp.where(col < valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_k", "interpret"))
def decode_attention_bhd(q, k, v, valid_len, *, scale: float,
                         block_k: int = 512, interpret: bool = False):
    """q (B, H, 1, hd), k/v (B, KV, hd, S), valid_len scalar int32
    -> (B, H, 1, hd). K and V blocks are (hd, ``kv_block(S, block_k)``)."""
    B, H, _, hd = q.shape
    KV, S = k.shape[1], k.shape[3]
    G = H // KV
    block_k = kv_block(S, block_k)
    lens = jnp.asarray(valid_len, jnp.int32).reshape(1)
    grid = (B, H, S // block_k)

    def kv_map(b, h, ik, lens):
        last = jnp.maximum(lens[0] - 1, 0) // block_k
        return (b, h // G, 0, jnp.minimum(ik, last))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, hd, block_k), kv_map),
            pl.BlockSpec((1, 1, hd, block_k), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda b, h, ik, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(lens, q, k, v)
    return out
