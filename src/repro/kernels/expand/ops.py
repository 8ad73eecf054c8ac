"""Jit'd public wrapper for the expansion kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from .kernel import expand_degrees_pallas


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def expand_degrees(adj: jnp.ndarray, states: jnp.ndarray, *, n: int,
                   block: int = 8, interpret: bool | None = None):
    """Degrees deg_S(v) for a batch of states.

    adj: (n, W) uint32; states: (B, W) uint32 -> (B, n) int32.
    """
    if interpret is None:
        interpret = default_interpret()
    return expand_degrees_pallas(adj, states, n=n, block=block,
                                 interpret=interpret)
