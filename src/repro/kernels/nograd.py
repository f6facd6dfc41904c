"""Forward-only guard for the Pallas kernels.

None of the kernels has a backward pass. Without a guard, ``jax.grad``
through a ``pallas_call`` fails deep inside JAX with a bare
``AssertionError``; with it, the error names the kernel and the way out.
"""
from __future__ import annotations

import jax


def forward_only(name: str, fn, *args):
    """``fn(*args)``, with a VJP that raises a clear error naming ``name``."""
    @jax.custom_vjp
    def call(*xs):
        return fn(*xs)

    def fwd(*xs):
        return fn(*xs), None

    def bwd(_, g):
        raise NotImplementedError(
            f"the Pallas {name} kernel has no backward pass: differentiate "
            "the model with use_pallas=False (the XLA path)")

    call.defvjp(fwd, bwd)
    return call(*args)
