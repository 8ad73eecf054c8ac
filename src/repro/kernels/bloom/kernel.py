"""Pallas TPU kernel: Bloom-filter insert/query with atomic-OR semantics.

The paper leans on the GPU's hardware atomic OR plus 65 536 mutexes to make
concurrent inserts of the *same* element safe (§3.2).  TPUs expose no
atomics through XLA; the TPU-native equivalent used here is **sequential
grid semantics**: Pallas grid steps execute in order on a core, so inserts
within a kernel invocation are serialised by construction and the
mutex/false-negative problem disappears.  Across devices, the distributed
solver hash-partitions states so each filter shard has a single writer
(DESIGN.md §2) — ownership replaces atomicity.

The filter is bit-packed uint32 (as on the GPU), viewed as (T, 8, 128)
vreg tiles and held in VMEM for the whole call; it is updated in place via
input/output aliasing.  A probe is a vector read-modify-write of the one
tile that holds its word: load the tile, test and set the bit under a
one-hot (sublane, lane) mask, store the tile back.  The murmur3 hashes of
the rows are computed by ``repro.core.bloom.murmur3_words`` before the
call (one XLA pass) and read per row from SMEM with the valid flags.

Random probes into a multi-megabyte filter are the one part of the
paper's design that has no efficient TPU analogue — which is exactly why
the framework's default dedup is the sort-based one (see dedup.py).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bloom import SEED1, SEED2, murmur3_words
from repro.kernels.common import U32

TILE_WORDS = 8 * 128


def _bloom_kernel(rows_ref, filt_in_ref, new_ref, filt_ref, *, m_bits: int,
                  k_hashes: int, block: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        filt_ref[...] = filt_in_ref[...]

    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def insert_one(i, carry):
        h1, h2, valid = rows_ref[0, i], rows_ref[1, i], rows_ref[2, i]

        def probe(j, any_zero):
            idx = (h1 + j.astype(U32) * h2) % np.uint32(m_bits)
            word = idx >> np.uint32(5)
            tile = (word >> np.uint32(10)).astype(jnp.int32)
            at = ((sub == ((word >> np.uint32(7)) & np.uint32(7))
                   .astype(jnp.int32))
                  & (lane == (word & np.uint32(127)).astype(jnp.int32)))
            bit = jnp.where(at, np.uint32(1) << (idx & np.uint32(31)),
                            np.uint32(0))
            old = filt_ref[tile]
            seen = jnp.max(jnp.where((old & bit) != 0, 1, 0))
            filt_ref[tile] = old | bit
            return any_zero | (seen == 0)

        new_ref[0, i] = 0

        @pl.when(valid != 0)                 # invalid rows probe nothing
        def _():
            any_zero = jax.lax.fori_loop(0, k_hashes, probe, False)
            new_ref[0, i] = any_zero.astype(jnp.int32)

        return carry

    jax.lax.fori_loop(0, block, insert_one, 0)


@functools.partial(jax.jit, static_argnames=("m_bits", "k_hashes", "block",
                                             "interpret"))
def bloom_insert_pallas(filter_words: jnp.ndarray, states: jnp.ndarray,
                        valid: jnp.ndarray, *, m_bits: int,
                        k_hashes: int = 17, block: int = 256,
                        interpret: bool):
    """Sequentially insert ``states`` rows; returns (was_new (B,), filter).

    filter_words (m_bits / 32,) uint32; states (B, W); valid (B,).  Rows
    are padded to a multiple of ``block`` (rows per grid step) with
    invalid rows.
    """
    b = states.shape[0]
    m_words = filter_words.shape[0]
    pad = (-b) % block
    rows = jnp.stack([murmur3_words(states, SEED1),
                      murmur3_words(states, SEED2),
                      valid.astype(U32)])
    rows = jnp.pad(rows, ((0, 0), (0, pad)))
    tiles = -(-m_words // TILE_WORDS)
    filt = jnp.pad(filter_words, (0, tiles * TILE_WORDS - m_words))
    kernel = functools.partial(_bloom_kernel, m_bits=m_bits,
                               k_hashes=k_hashes, block=block)
    whole_filter = pl.BlockSpec((tiles, 8, 128), lambda i: (0, 0, 0))
    was_new, filt = pl.pallas_call(
        kernel,
        grid=((b + pad) // block,),
        in_specs=[
            pl.BlockSpec((3, block), lambda i: (0, i),
                         memory_space=pltpu.SMEM),      # h1, h2, valid
            whole_filter,                               # filter (aliased)
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i),
                         memory_space=pltpu.SMEM),
            whole_filter,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, b + pad), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 8, 128), U32),
        ],
        input_output_aliases={1: 1},
        interpret=interpret,
    )(rows, filt.reshape(tiles, 8, 128))
    return was_new[0, :b].astype(jnp.bool_), filt.reshape(-1)[:m_words]
