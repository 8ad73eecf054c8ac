"""Jit'd public wrapper for the Bloom kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from .kernel import bloom_insert_pallas


def make_filter_words(m_bits: int) -> jnp.ndarray:
    assert m_bits % 32 == 0
    return jnp.zeros((m_bits // 32,), dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("m_bits", "k_hashes", "block",
                                             "interpret"))
def bloom_insert(filter_words, states, valid, *, m_bits: int,
                 k_hashes: int = 17, block: int = 256,
                 interpret: bool | None = None):
    """Insert states (B, W) into the packed filter; returns (was_new, filter)."""
    if interpret is None:
        interpret = default_interpret()
    return bloom_insert_pallas(
        filter_words, states, valid, m_bits=m_bits, k_hashes=k_hashes,
        block=block, interpret=interpret)
