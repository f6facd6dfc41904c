"""Jit-facing entry point for the SSD scan.

Routes to the Pallas TPU kernel (``use_pallas=True``; interpret mode supported
for CPU validation) or to the chunked pure-jnp implementation (the XLA
production path used for dry-run compiles on this container).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from ..nograd import forward_only


@partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret",
                                  "precision", "return_state"))
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256, use_pallas: bool = False,
        interpret: bool = False, h0: Optional[jnp.ndarray] = None,
        precision: str = "highest", return_state: bool = True
        ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """SSD scan. See kernels/ssd/ref.py for shapes. The final state is None
    unless ``return_state``: a caller that discards it spares the kernel
    computing and writing it."""
    if use_pallas:
        from .ssd import ssd_pallas
        return forward_only(
            "ssd",
            lambda *a: ssd_pallas(*a, chunk=chunk, interpret=interpret,
                                  h0=h0, return_state=return_state),
            x, dt, A, Bm, Cm)
    y, h_final = ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                                 precision=precision)
    return y, (h_final if return_state else None)
