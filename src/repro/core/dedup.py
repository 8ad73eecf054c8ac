"""Exact duplicate elimination by multi-word sort (beyond-paper mode).

The paper dedups with a Bloom filter because GPUs have fast atomic OR and
sorting 180M states on a 2017 GPU was unattractive.  TPUs sort well and XLA
sorts are deterministic, so the framework's default dedup is an exact
lexicographic sort over the packed state words + neighbour-difference mask +
stream compaction.  Zero false positives -> the solver stays Las Vegas
instead of Monte Carlo.  The Bloom path (paper-faithful) lives in bloom.py.

Invalid rows are replaced by the all-ones sentinel, which sorts last and can
never equal a real state (a state of size n is never generated: the DP stops
at ``n - max(k+1, |C|)`` eliminated vertices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

U32 = jnp.uint32
SENTINEL = jnp.uint32(0xFFFFFFFF)


def sort_states(keys: jnp.ndarray, valid: jnp.ndarray):
    """Lexicographically sort rows of (M, W) with invalid rows sent to the end.

    Returns (sorted_keys (M, W), sorted_valid (M,))."""
    m, w = keys.shape
    keys = jnp.where(valid[:, None], keys, SENTINEL)
    cols = tuple(keys[:, j] for j in range(w)) + (valid,)
    out = jax.lax.sort(cols, dimension=0, num_keys=w)
    sorted_keys = jnp.stack(out[:w], axis=1)
    return sorted_keys, out[w]


def unique_mask(sorted_keys: jnp.ndarray, sorted_valid: jnp.ndarray):
    """First-occurrence mask over sorted rows."""
    diff = jnp.any(sorted_keys[1:] != sorted_keys[:-1], axis=1)
    first = jnp.concatenate([jnp.ones((1,), dtype=bool), diff])
    return first & sorted_valid


def compact(rows: jnp.ndarray, keep: jnp.ndarray, out: jnp.ndarray,
            offset=0):
    """Write the kept rows of ``rows``, in order, into ``out`` at ``offset``.

    Returns (out, n_written, n_dropped).  Kept rows that would land past
    the buffer are dropped and counted (the paper's list-overflow
    semantics).  Rows below ``offset`` stay; the ``len(rows)``-row window
    from ``offset`` is zero after the written rows, as frontier buffers
    are past their count.

    No per-row index: a sort keyed on the row number (kept rows) or
    ``len(rows)`` (the rest) moves the kept rows to the front in order,
    and one ``dynamic_update_slice`` writes them as a window (under
    ``vmap``, one index per lane).  A window that would run past the
    buffer starts earlier, with the rows shifted down behind the rows of
    ``out`` it covers."""
    m, w = rows.shape
    cap = out.shape[0]
    mw = min(m, cap)                      # rows past cap never land
    offset = jnp.asarray(offset, jnp.int32)
    n_keep = jnp.sum(keep.astype(jnp.int32))
    written = jnp.minimum(n_keep, jnp.maximum(0, cap - offset))
    dropped = n_keep - written

    key = jnp.where(keep, jnp.arange(m, dtype=jnp.int32), m)
    cols = jax.lax.sort((key,) + tuple(rows[:, j] for j in range(w)),
                        dimension=0, num_keys=1, is_stable=False)
    packed = jnp.where((cols[0][:mw] < m)[:, None],
                       jnp.stack([c[:mw] for c in cols[1:]], axis=1), 0)

    start = jnp.clip(offset, 0, cap - mw)
    shift = jnp.clip(offset - start, 0, mw)
    padded = jnp.concatenate([jnp.zeros((mw, w), out.dtype),
                              packed.astype(out.dtype)])
    shifted = jax.lax.dynamic_slice(padded, (mw - shift, 0), (mw, w))
    old = jax.lax.dynamic_slice(out, (start, 0), (mw, w))
    below = (jnp.arange(mw, dtype=jnp.int32) < shift)[:, None]
    window = jnp.where(below, old, shifted)
    out = jax.lax.dynamic_update_slice(out, window, (start, 0))
    return out, written, dropped


@functools.partial(jax.jit, static_argnames=("cap",))
def dedup_compact(keys: jnp.ndarray, valid: jnp.ndarray, cap: int):
    """Sort-dedup rows and compact into a fresh (cap, W) frontier buffer.

    Returns (buffer, count, dropped)."""
    sk, sv = sort_states(keys, valid)
    keep = unique_mask(sk, sv)
    w = keys.shape[-1]
    return compact(sk, keep, jnp.zeros((cap, w), dtype=U32))
