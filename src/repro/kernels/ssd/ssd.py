"""Mamba2 SSD chunked scan as a Pallas TPU kernel (arXiv:2405.21060 §6,
re-tiled for TPU).

Grid (batch, head block, chunks) with chunks innermost/sequential. A head
block is ``hb`` heads of one B/C group, so each grid step computes C B^T
(L x L, f32) once and reuses it for every head of the block; the decay, the
quasi-attention product and the carried state stay per head:

    att = (C B^T) * exp(cum_i - cum_j) * dt_j   (L x L, causal-masked)
    y   = att @ x + (C * exp(cum)) @ state^T
    state = exp(cum_L) * state + x^T (decay_to_end * dt * B)

x and y move in the model's own (B, S, H*P) layout, a lane block of hb*P
columns per step, and dt in its own (B, S, H) layout, so no layout copy
surrounds the call. The running state (hb, P, N, f32) lives in VMEM scratch
when a later chunk reads it, and the final state is written only when the
caller asks for it. Chunk length L and state width N are MXU-aligned
(256/128 by default); the decay/cumsum math and every matmul operand are f32.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM for one grid step's blocks (double-buffered) and state scratch; the
# rest of the chip's scoped VMEM holds the per-head L x L temporaries
_VMEM_BUDGET = 8 * 2**20
# the exactly summed part of the in-chunk cumsum is a multiple of
# 1 / _HI_STEPS (see _ssd_kernel)
_HI_STEPS = 256.0


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def head_block(H: int, G: int, P: int, N: int, L: int, x_bytes: int,
               bc_bytes: int) -> int:
    """Heads per grid step: the largest divisor of H // G whose blocks and
    scratch fit _VMEM_BUDGET, preferring those whose hb*P columns the chip
    can tile (a multiple of 128 lanes, or all H heads)."""
    per_group = H // G

    def vmem(hb):
        lanes = _round_up(hb, 128)
        return (2 * 2 * L * hb * P * x_bytes           # x and y blocks
                + 2 * 2 * L * N * bc_bytes             # B and C blocks
                + 2 * (L + 8) * lanes * 4              # dt and A blocks
                + 3 * hb * P * N * 4)                  # final + running state

    fits = [d for d in range(1, per_group + 1)
            if per_group % d == 0 and vmem(d) <= _VMEM_BUDGET]
    tiled = [d for d in fits if (d * P) % 128 == 0 or d == H]
    return max(tiled or fits or [1])


def _cumsum0(v: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum along axis 0 (Mosaic lowers no cumsum):
    log2(rows) shifted adds, every column at once."""
    n = v.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    s = 1
    while s < n:
        v = v + jnp.where(row >= s, pltpu.roll(v, s, 0), 0.0)
        s *= 2
    return v


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, *rest,
                hb: int, P: int, n_chunks: int, return_state: bool):
    hf_ref = rest[0] if return_state else None
    state_scr = rest[-1] if n_chunks > 1 else None
    ic = pl.program_id(2)

    if state_scr is not None:
        @pl.when(ic == 0)
        def _init():
            state_scr[...] = jnp.zeros_like(state_scr)

    B = b_ref[0, 0].astype(jnp.float32)            # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)            # (L, N)
    dt = dt_ref[0, 0]                              # (L, hb) f32
    L = dt.shape[0]

    # in-chunk inclusive cumsum of dA for every head of the block, split as
    # hi + lo: hi sums dA rounded to multiples of 2^-8, exactly in f32 (for
    # |cum| < 2^16), so hi_i - hi_j has no rounding error; lo (|lo| < 0.5)
    # carries the rest. exp(cum_i - cum_j) is then
    # exp(hi_i - hi_j) * exp(lo_i) * exp(-lo_j), free of the cancellation
    # between two large cumsums
    dA = dt * a_ref[0]                             # (L, hb), negative
    dA_hi = jnp.floor(dA * _HI_STEPS + 0.5) * (1.0 / _HI_STEPS)
    hi = _cumsum0(dA_hi)
    lo = _cumsum0(dA - dA_hi)
    # decay needs cum along sublanes (i) and along lanes (j)
    hi_t = hi.T                                    # (hb, L)
    lo_t = lo.T
    row_scale = (dt * jnp.exp(-lo)).T              # (hb, L): dt_j exp(-lo_j)
    col_scale = jnp.exp(lo)                        # (L, hb): exp(lo_i)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    # shared by every head of the block (one B/C group)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (L, L)

    for j in range(hb):
        cols = slice(j * P, (j + 1) * P)
        hi_i = hi[:, j:j + 1]                      # (L, 1)
        # exp(hi_i - hi_j) for j <= i; masked before exp, as above the
        # diagonal the exponent is positive and overflows
        decay = jnp.exp(jnp.where(causal, hi_i - hi_t[j:j + 1, :],
                                  -jnp.inf))
        att = cb * decay * row_scale[j:j + 1, :]
        x = x_ref[0, :, cols].astype(jnp.float32)  # (L, P)
        y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = y * col_scale[:, j:j + 1]

        if state_scr is not None:
            # inter-chunk contribution from the carried state (zero at the
            # first chunk)
            state = state_scr[j]                   # (P, N)
            y = y + jax.lax.dot_general(
                C * jnp.exp(hi_i + lo[:, j:j + 1]), state,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        y_ref[0, :, cols] = y.astype(y_ref.dtype)

        if state_scr is None and not return_state:
            continue
        # state update: x_i decayed to the chunk's end, exp(cum_L - cum_i)
        hi_end, lo_end = hi[L - 1:L, j:j + 1], lo[L - 1:L, j:j + 1]
        to_end = (jnp.exp(hi_end - hi_i) * jnp.exp(lo_end - lo[:, j:j + 1])
                  * dt[:, j:j + 1])                # (L, 1)
        new_state = jax.lax.dot_general(x * to_end, B,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        if state_scr is not None:
            # exp(cum_L) as a lane reduction: Mosaic broadcasts its (1, 1)
            # result over (P, N), where a sliced (1, 1) would not lower
            total = jnp.sum(jnp.where(col[:1] == L - 1, hi_t[j:j + 1, :]
                                      + lo_t[j:j + 1, :], 0.0),
                            axis=1, keepdims=True)
            new_state = state * jnp.exp(total) + new_state
            state_scr[j] = new_state
        if return_state:
            @pl.when(ic == n_chunks - 1)
            def _emit_state():
                hf_ref[0, j] = new_state


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "return_state"))
def ssd_pallas(x, dt, A, Bm, Cm, *, chunk: int = 256,
               interpret: bool = False,
               h0: Optional[jnp.ndarray] = None,
               return_state: bool = True
               ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Shapes as kernels/ssd/ref.py. h0 must be None (training path). The
    final state is None unless ``return_state``."""
    assert h0 is None, "ssd_pallas: initial state not supported (use ref)"
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad dt with zeros -> exp(0*A)=1, B=0: padding is a no-op for state
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // L
    hb = head_block(H, G, P, N, L, x.dtype.itemsize, Bm.dtype.itemsize)
    nhb = H // hb
    blocks_per_group = (H // G) // hb

    # (B, Sp, H*P) is x's own layout; dt and A become (.., head block, .., hb)
    # and B/C (B, G, Sp, N): reshapes, and moves of a size-1 axis when
    # nhb == 1 and G == 1
    x2 = x.reshape(B, Sp, H * P)
    dtb = jnp.swapaxes(dt.astype(jnp.float32).reshape(B, Sp, nhb, hb), 1, 2)
    Ab = A.astype(jnp.float32).reshape(nhb, 1, hb)
    Bt = jnp.swapaxes(Bm, 1, 2)
    Ct = jnp.swapaxes(Cm, 1, 2)

    group = lambda b, k, c: (b, k // blocks_per_group, c, 0)   # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, hb), lambda b, k, c: (k, 0, 0)),
        pl.BlockSpec((1, L, hb * P), lambda b, k, c: (b, c, k)),
        pl.BlockSpec((1, 1, L, hb), lambda b, k, c: (b, k, c, 0)),
        pl.BlockSpec((1, 1, L, N), group),
        pl.BlockSpec((1, 1, L, N), group),
    ]
    out_specs = [pl.BlockSpec((1, L, hb * P), lambda b, k, c: (b, c, k))]
    out_shape = [jax.ShapeDtypeStruct((B, Sp, H * P), x.dtype)]
    if return_state:
        out_specs.append(pl.BlockSpec((1, hb, P, N),
                                      lambda b, k, c: (b, k, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, P, N), jnp.float32))
    scratch = [pltpu.VMEM((hb, P, N), jnp.float32)] if nc > 1 else []

    outs = pl.pallas_call(
        functools.partial(_ssd_kernel, hb=hb, P=P, n_chunks=nc,
                          return_state=return_state),
        grid=(B, nhb, nc),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(Ab, x2, dtb, Bt, Ct)

    y = outs[0].reshape(B, Sp, H, P)[:, :S]
    return y, (outs[1] if return_state else None)
