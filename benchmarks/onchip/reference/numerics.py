"""Matrix products of the plain references, in the precision asked for.

``"f32"``: float32 operands at full matmul precision, the reference itself.
``"fp8"``: the control, one step below the bfloat16 that the configurations
state. Each operand is scaled so that its largest magnitude is the largest
finite value of its float8 type and rounded to it: float8_e4m3fn for
activations and weights, float8_e5m2 for gradients in the backward pass,
as float8 training does. Products accumulate in float32 and are scaled
back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2


def through(x, dtype):
    """``x`` rounded to ``dtype`` and handed back in float32. The barrier
    keeps the two conversions apart: without it XLA on the TPU, which may
    keep more precision than asked for, drops the pair and the rounding
    with it."""
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(jnp.float32)


def _q(a, fmt):
    """``a`` rounded to ``fmt`` under a per-tensor scale, and that scale."""
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, float(jnp.finfo(fmt).max) / amax, 1.0)
    return through(a * scale, fmt), scale


def _mm8(a, b, fa, fb):
    a8, sa = _q(a, fa)
    b8, sb = _q(b, fb)
    # float8 values are exact in float32, so this is the float8 product
    return jnp.matmul(a8, b8, precision=jax.lax.Precision.HIGHEST) / (sa * sb)


@jax.custom_vjp
def _dot8(a, b):
    return _mm8(a, b, E4M3, E4M3)


def _dot8_fwd(a, b):
    return _dot8(a, b), (a, b)


def _dot8_bwd(res, g):
    a, b = res
    a2 = a.reshape(-1, a.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    da = _mm8(g2, b.T, E5M2, E4M3).reshape(a.shape)
    db = _mm8(a2.T, g2, E4M3, E5M2)
    return da, db


_dot8.defvjp(_dot8_fwd, _dot8_bwd)


def dot(a, b, prec: str):
    """``a @ b`` over the last axis of ``a`` and the first of ``b`` (a
    matrix)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if prec == "fp8":
        return _dot8(a, b)
    raise ValueError(f"unknown reference precision {prec!r}")


def round_to(x, dtype: str):
    """Round float32 values to ``dtype`` and back: a parameter stored in the
    configuration's type keeps only what that type holds."""
    return through(x, jnp.dtype(dtype))
