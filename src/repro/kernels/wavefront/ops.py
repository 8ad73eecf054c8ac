"""Jit'd public wrapper for the fused wavefront kernel.

``wavefront_expand`` is the pallas implementation of the backend registry's
``wavefront_expand`` op (see ``repro.core.backend``): same signature as the
jax reference composition in ``repro.core.expand.wavefront_expand``, same
outputs bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from .kernel import wavefront_pallas


@functools.partial(jax.jit, static_argnames=("n", "schedule", "use_mmw",
                                             "use_simplicial", "block",
                                             "interpret"))
def wavefront_expand(adj, states, valid, k, allowed, *, n: int,
                     schedule: str = "doubling", use_mmw: bool = False,
                     use_simplicial: bool = False, block: int = 8,
                     interpret: bool | None = None):
    """Fused expand + feasibility + pruning.

    adj (n, W) uint32; states (B, W) uint32; valid (B,) bool; k scalar
    int32; allowed (W,) uint32 -> (children (B, n, W), feasible (B, n) bool).
    """
    if schedule != "doubling":
        # the registry rejects this combination before dispatch; this guard
        # catches direct callers
        raise ValueError(
            f"pallas wavefront kernel has one fixed closure algorithm and "
            f"accepts only schedule='doubling'; schedule={schedule!r} is "
            f"jax-only")
    if interpret is None:
        interpret = default_interpret()
    kdev = jnp.asarray(k, jnp.int32).reshape(1, 1)
    children, feas = wavefront_pallas(
        adj, states, valid, kdev, allowed, n=n, block=block,
        use_mmw=use_mmw, use_simplicial=use_simplicial, interpret=interpret)
    return children, feas.astype(jnp.bool_)
