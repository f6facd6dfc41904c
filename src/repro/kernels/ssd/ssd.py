"""Mamba2 SSD chunked scan as a Pallas TPU kernel (arXiv:2405.21060 §6,
re-tiled for TPU).

Grid (batch, heads, chunks) with chunks innermost/sequential: the running
state (P x N, f32) lives in VMEM scratch and carries across chunk iterations
(the inter-chunk linear recurrence), while each iteration computes the
intra-chunk "quasi-attention" term on the MXU:

    att = (C B^T) * exp(cum_i - cum_j) * dt_j   (L x L, causal-masked)
    y   = att @ x + (C * exp(cum)) @ state^T
    state = exp(cum_L) * state + x^T (decay_to_end * dt * B)

Chunk length L and state width N are MXU-aligned (256/128 by default); the
decay/cumsum math is f32 throughout. The B/C group mapping (head -> group)
is expressed in the index_map, so grouped B/C are never materialized per
head in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, hf_ref,
                state_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    A = a_ref[pl.program_id(1)]                    # per-head scalar (SMEM)
    x = x_ref[0, 0].astype(jnp.float32)            # (L, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)     # (L, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)     # (1, L)
    B = b_ref[0, 0].astype(jnp.float32)            # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)            # (L, N)

    L = chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    # inclusive in-chunk cumsum of dA in both layouts, as masked reductions
    # of broadcasts (Mosaic lowers neither cumsum nor an (L,1)<->(1,L)
    # relayout): cum[i] = sum_{j<=i} dA_j, once along sublanes, once along
    # lanes
    dA_col = dt_col * A                            # (L, 1), negative
    dA_row = dt_row * A                            # (1, L)
    cum_col = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(row <= col, dA_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(dA_row, axis=1, keepdims=True)  # (1, 1) = cum[L-1]

    # ---- intra-chunk quasi-attention ---------------------------------------
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (L, L)
    # exp(cum_i - cum_j) for j <= i; masked before exp, as above the
    # diagonal the exponent is positive and overflows
    decay = jnp.exp(jnp.where(causal, cum_col - cum_row, -jnp.inf))
    att = cb * decay * dt_row
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (L, P)

    # ---- inter-chunk contribution from the carried state --------------------
    state = state_scr[...]                         # (P, N)
    c_scaled = C * jnp.exp(cum_col)                # (L, N)
    y = y + jax.lax.dot_general(c_scaled, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # ---- state update ---------------------------------------------------------
    xw = x * (jnp.exp(total - cum_col) * dt_col)   # (L, P), decay to chunk end
    new_state = state * jnp.exp(total) + jax.lax.dot_general(
        xw, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (P, N)
    state_scr[...] = new_state

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        hf_ref[0, 0] = new_state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bm, Cm, *, chunk: int = 256,
               interpret: bool = False,
               h0: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shapes as kernels/ssd/ref.py. h0 must be None (training path)."""
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
    grp = H // G

    # kernel-friendly layouts: (B, H|G, nc*L, ...) with heads outside seq;
    # dt goes in twice, as a column and as a row, so the kernel never
    # relayouts between the two
    xt = jnp.swapaxes(x, 1, 2)                      # (B, H, Sp, P)
    dtf = jnp.swapaxes(dt, 1, 2).astype(jnp.float32)
    dt_col = dtf[..., None]                         # (B, H, Sp, 1)
    dt_row = dtf[:, :, None, :]                     # (B, H, 1, Sp)
    Bt = jnp.swapaxes(Bm, 1, 2)                     # (B, G, Sp, N)
    Ct = jnp.swapaxes(Cm, 1, 2)
    Af = A.astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                      # A, one scalar per head
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c, a: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, c, a: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c, a: (b, h, 0, c)),
            pl.BlockSpec((1, 1, L, N),
                         lambda b, h, c, a, grp=grp: (b, h // grp, c, 0)),
            pl.BlockSpec((1, 1, L, N),
                         lambda b, h, c, a, grp=grp: (b, h // grp, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c, a: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c, a: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    y, h_final = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=L, n_chunks=nc),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(Af, xt, dt_col, dt_row, Bt, Ct)

    y = jnp.swapaxes(y, 1, 2)[:, :S]                # (B, S, H, P)
    return y, h_final
