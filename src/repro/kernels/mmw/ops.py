"""Jit'd wrapper for the MMW kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from .kernel import mmw_bounds_pallas


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def mmw_bounds(reach, states, k, *, n: int, block: int = 8,
               interpret: bool | None = None):
    """MMW lower bounds: reach (B, n, W), states (B, W), k -> (B,) int32."""
    if interpret is None:
        interpret = default_interpret()
    k = jnp.asarray(k, jnp.int32).reshape(1, 1)
    return mmw_bounds_pallas(reach, states, k, n=n, block=block,
                             interpret=interpret)
