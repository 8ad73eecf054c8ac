"""Shared lane layout and in-kernel bitset helpers for the Pallas kernels.

TPU vector registers are (8, 128) tiles of 32-bit words, and a packed state
is only W = ceil(n/32) words wide, so the kernels never put a state's words
on the lane axis.  They put the *states* there instead: a block of states
is held word-major as W arrays of shape (S, 128) — one word of 128·S
states per array, one vreg per word at S = 8 — and every bitset operation
is elementwise across states.  A matrix of per-vertex rows (closure,
reach, Q sets) is a VMEM ref of shape (n, W, S, 128) indexed on its
untiled leading dimensions.  Adjacency, the candidate mask and k are the
same for every state; they live in SMEM and are read as scalars.

``to_lanes`` / ``from_lanes`` convert between the engine's row-major
(B, ...) arrays and this layout outside the kernel; ``lane_geometry``
picks the padded row count and the rows per grid step.  Inside a kernel,
a per-vertex operation acts on all n rows at once along the leading axis
(``unpack`` and ``eye`` build those rows from iota and shifts, never a
gather), and a loop over pivot vertices is a ``fori_loop`` over the bits
of one word (``pivot_loop``), so the word a pivot lives in is static.

The results are identical bit for bit to ``repro.core.bitset`` /
``repro.core.components``; the parity tests in tests/test_kernels_*.py
pin that.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

U32 = jnp.uint32
LANES = 128


def lane_geometry(b: int, block: int):
    """Rows of 128 states for ``b`` states, and rows per grid step.

    ``block`` is the rows per step (8 = one (8, 128) int32 tile per word);
    a batch that fits in one step runs as a single full-array block, so
    small batches are padded to 128 states and no further.  Returns
    (rows, rows_per_step) with rows a multiple of rows_per_step."""
    rows = max(1, -(-b // LANES))
    step = max(1, min(block, rows))
    return -(-rows // step) * step, step


def to_lanes(x, rows: int):
    """(B, *rest) -> (*rest, rows, 128), zero-padded past B."""
    b = x.shape[0]
    x = jnp.pad(x, [(0, rows * LANES - b)] + [(0, 0)] * (x.ndim - 1))
    return jnp.moveaxis(x, 0, -1).reshape(x.shape[1:] + (rows, LANES))


def from_lanes(y, b: int):
    """(*rest, rows, 128) -> (b, *rest): the inverse of ``to_lanes``."""
    y = y.reshape(y.shape[:-2] + (-1,))
    return jnp.moveaxis(y, -1, 0)[:b]


def lane_tile(step: int, *lead: int):
    """BlockSpec of one grid step's (*lead, step, 128) slab of states."""
    return pl.BlockSpec((*lead, step, LANES),
                        lambda i: (0,) * len(lead) + (i, 0))


def smem(shape):
    """BlockSpec pinning a whole small array in SMEM for every grid step."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                        memory_space=pltpu.SMEM)


def word_bits(n: int, j: int) -> range:
    """The vertex bits held by word ``j`` of an n-vertex bitset."""
    return range(min(32, n - 32 * j))


def full_word(n: int, j: int) -> int:
    """Word ``j`` of the universe {0..n-1}, as a Python int."""
    return (1 << len(word_bits(n, j))) - 1 if 32 * j < n else 0


def mask_of(bit):
    """0/1 uint32 -> all-zeros / all-ones word (scalar or vector)."""
    return np.uint32(0) - bit


def vertex_iota(n: int, like):
    """(n, *like.shape) int32 holding v along the leading vertex axis."""
    return jax.lax.broadcasted_iota(jnp.int32, (n,) + like.shape, 0)


def unpack(words, n: int):
    """W word arrays (S, 128) -> (n, S, 128) uint32 0/1 with bit v of every
    state at row v.  Built from iota and shifts: no gather."""
    vid = vertex_iota(n, words[0])
    sh = (vid & 31).astype(U32)
    out = words[0][None] >> sh
    for j in range(1, len(words)):
        out = jnp.where((vid >> 5) == j, words[j][None] >> sh, out)
    return out & np.uint32(1)


def eye(n: int, j: int, like):
    """(n, S, 128): word ``j`` of the singleton {v} at row v."""
    vid = vertex_iota(n, like)
    return jnp.where((vid >> 5) == j,
                     np.uint32(1) << (vid & 31).astype(U32), np.uint32(0))


def onehot(i, j: int):
    """Word ``j`` of the singleton {i} for per-state vertex ids ``i``."""
    return jnp.where((i >> 5) == j,
                     np.uint32(1) << (i & 31).astype(U32), np.uint32(0))


def popcount(words):
    """Set size of a word-major bitset (list of W arrays) -> int32."""
    total = jax.lax.population_count(words[0]).astype(jnp.int32)
    for word in words[1:]:
        total = total + jax.lax.population_count(word).astype(jnp.int32)
    return total


def pivot_loop(n: int, w: int, body) -> None:
    """Run ``body(c, word, shift)`` for every vertex c in order, as one
    ``fori_loop`` per word so that the word holding pivot c is static."""
    for cw in range(w):
        def step(b, carry, cw=cw):
            body(32 * cw + b, cw, b.astype(U32))
            return carry
        jax.lax.fori_loop(0, len(word_bits(n, cw)), step, 0)


def eye_words(n, w):
    """(n, W) identity bitset matrix, built from iota (capture-free)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, w), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, w), 1)
    return jnp.where(cols == (rows >> 5),
                     U32(1) << (rows & 31).astype(U32), U32(0))


def default_interpret() -> bool:
    """The one place that picks interpret mode: native Pallas on a TPU,
    the interpreter on every other backend (the CPU test runs)."""
    return jax.default_backend() != "tpu"
