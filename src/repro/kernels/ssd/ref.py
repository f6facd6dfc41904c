"""Pure-jnp oracles for the Mamba2 SSD (state-space duality) scan.

``ssd_naive``   — per-timestep linear recurrence via lax.scan (the ground truth).
``ssd_chunked`` — the SSD blocked algorithm (arXiv:2405.21060 §6) in plain jnp;
                  this is the XLA production path and the structural template
                  the Pallas kernel mirrors.

Shapes (G = #B/C groups, heads map to groups by h // (H // G)):
  x  (B, S, H, P)   dt (B, S, H)  [post-softplus, > 0]
  A  (H,)           [negative]
  Bm (B, S, G, N)   Cm (B, S, G, N)
  h0 (B, H, P, N)   [optional initial state]
returns y (B, S, H, P), h_final (B, H, P, N)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _expand_groups(t: jnp.ndarray, H: int) -> jnp.ndarray:
    """(B, S, G, N) -> (B, S, H, N) by repeating each group H//G times."""
    G = t.shape[2]
    return jnp.repeat(t, H // G, axis=2)


def ssd_naive(x, dt, A, Bm, Cm, h0: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Bh = _expand_groups(Bm, H).astype(jnp.float32)
    Ch = _expand_groups(Cm, H).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    h = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp          # (B,H,P), (B,H), (B,H,N), (B,H,N)
        dA = jnp.exp(dt_t * Af)            # (B,H)
        h = h * dA[..., None, None] + (dt_t[..., None, None]
                                       * x_t[..., None] * B_t[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t)
        return h, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    h, ys = jax.lax.scan(step, h, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    return y, h


def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int = 256,
                h0: Optional[jnp.ndarray] = None,
                precision: str = "highest"
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """precision='highest': all math f32 (oracle-grade). 'mixed': decay /
    cumsum / state stay f32, but the large matmul operands (CB^T, att@x)
    stay in the input dtype — the perf-iteration variant (EXPERIMENTS.md
    §Perf): ~2x less bytes through the dominant intermediates."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // L
    mm_dtype = jnp.float32 if precision == "highest" else x.dtype

    xf = x.astype(mm_dtype).reshape(B, nc, L, H, P)
    dtf = dt.astype(jnp.float32).reshape(B, nc, L, H)
    Bh = _expand_groups(Bm, H).astype(mm_dtype).reshape(B, nc, L, H, N)
    Ch = _expand_groups(Cm, H).astype(mm_dtype).reshape(B, nc, L, H, N)
    Af = A.astype(jnp.float32)

    dA = dtf * Af                                   # (B,nc,L,H), negative
    cum = jnp.cumsum(dA, axis=2)                    # inclusive cumsum within chunk

    # ---- intra-chunk (the "quadratic attention" term) -----------------------
    # att[i, j] = C_i . B_j * exp(cum_i - cum_j) * dt_j   for j <= i
    cb = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh,
                    preferred_element_type=jnp.float32)  # (B,nc,H,L,L) l=i,s=j
    # decay[b,c,h,i,j] = exp(cum[b,c,i,h] - cum[b,c,j,h]) for j <= i, else 0.
    # The exponent is masked before exp: above the diagonal it is positive
    # and overflows to inf at real dt*A over a 256-token chunk, and the
    # gradient of a where() over inf is 0 * inf = nan
    idx = jnp.arange(L)
    causal = (idx[:, None] >= idx[None, :])
    seg = (cum[:, :, :, None, :].transpose(0, 1, 4, 2, 3)
           - cum.transpose(0, 1, 3, 2)[:, :, :, None, :])
    decay = jnp.exp(jnp.where(causal[None, None, None], seg, -jnp.inf))
    att = cb * decay
    att = att * dtf.transpose(0, 1, 3, 2)[:, :, :, None, :]     # * dt_j
    y_intra = jnp.einsum("bchls,bcshp->bclhp", att.astype(mm_dtype), xf,
                         preferred_element_type=jnp.float32)

    # ---- chunk summaries -> inter-chunk recurrence ----------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)             # (B,nc,L,H)
    # state contribution of chunk c: sum_j decay_to_end_j * dt_j * B_j (x) x_j
    Sc = jnp.einsum("bclh,bclhn,bclhp->bchpn",
                    (decay_to_end * dtf).astype(mm_dtype), Bh, xf,
                    preferred_element_type=jnp.float32)
    Gam = jnp.exp(cum[:, :, -1, :])                             # (B,nc,H)

    h_init = (jnp.zeros((B, H, P, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))

    def chunk_step(h, inp):
        Sc_c, Gam_c = inp
        h_next = h * Gam_c[..., None, None] + Sc_c
        return h_next, h                                        # emit state *before* chunk

    h_final, h_prev = jax.lax.scan(
        chunk_step, h_init,
        (jnp.moveaxis(Sc, 1, 0), jnp.moveaxis(Gam, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                         # (B,nc,H,P,N)

    # ---- inter-chunk output: y_i += C_i . (exp(cum_i) * h_prev) ---------------
    y_inter = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                         Ch.astype(jnp.float32), h_prev, jnp.exp(cum))

    y = (y_intra + y_inter).reshape(B, Sp, H, P)[:, :S].astype(x.dtype)
    return y, h_final


def ssd_step(x_t, dt_t, A, B_t, C_t, h):
    """Single decode step.

    x_t (B,H,P), dt_t (B,H), B_t/C_t (B,G,N), h (B,H,P,N) -> (y (B,H,P), h')
    """
    H = x_t.shape[1]
    G = B_t.shape[1]
    Bh = jnp.repeat(B_t, H // G, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C_t, H // G, axis=1).astype(jnp.float32)
    dA = jnp.exp(dt_t.astype(jnp.float32) * A.astype(jnp.float32))
    h = (h.astype(jnp.float32) * dA[..., None, None]
         + dt_t.astype(jnp.float32)[..., None, None]
         * x_t.astype(jnp.float32)[..., None] * Bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch)
    return y.astype(x_t.dtype), h
