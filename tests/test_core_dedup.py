import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dedup


def _np_unique_rows(rows, valid):
    live = rows[valid]
    return np.unique(live, axis=0) if len(live) else live


@given(st.integers(0, 10000), st.integers(1, 200), st.integers(1, 3))
@settings(max_examples=40, deadline=None)
def test_dedup_matches_numpy_unique(seed, m, w):
    rng = np.random.RandomState(seed)
    # small value range to force duplicates
    rows = rng.randint(0, 4, size=(m, w)).astype(np.uint32)
    valid = rng.rand(m) < 0.8
    cap = m + 4
    buf, count, dropped = dedup.dedup_compact(
        jnp.asarray(rows), jnp.asarray(valid), cap)
    want = _np_unique_rows(rows, valid)
    count = int(count)
    assert int(dropped) == 0
    assert count == len(want)
    got = np.asarray(buf)[:count]
    assert np.array_equal(np.sort(got, axis=0), np.sort(want, axis=0)) or \
        np.array_equal(got[np.lexsort(got.T[::-1])], want[np.lexsort(want.T[::-1])])


def test_overflow_drops_and_counts():
    rows = jnp.asarray(np.arange(40, dtype=np.uint32).reshape(20, 2))
    valid = jnp.ones((20,), dtype=bool)
    buf, count, dropped = dedup.dedup_compact(rows, valid, 8)
    assert int(count) == 8 and int(dropped) == 12


def test_all_invalid():
    rows = jnp.asarray(np.zeros((10, 2), dtype=np.uint32))
    valid = jnp.zeros((10,), dtype=bool)
    buf, count, dropped = dedup.dedup_compact(rows, valid, 16)
    assert int(count) == 0 and int(dropped) == 0


def test_duplicates_across_validity():
    rows = np.array([[1, 0], [1, 0], [2, 0], [2, 0], [3, 0]], dtype=np.uint32)
    valid = np.array([True, True, True, False, True])
    buf, count, dropped = dedup.dedup_compact(
        jnp.asarray(rows), jnp.asarray(valid), 8)
    assert int(count) == 3   # {1,2,3}
    got = set(map(tuple, np.asarray(buf)[:3].tolist()))
    assert got == {(1, 0), (2, 0), (3, 0)}


def _np_compact(rows, keep, out, offset):
    """The scatter semantics ``compact`` keeps: kept row i lands at
    ``offset + (kept rows before i)``; rows past the buffer are dropped."""
    out = out.copy()
    pos = offset + np.cumsum(keep) - 1
    for row, k, p in zip(rows, keep, pos):
        if k and p < len(out):
            out[p] = row
    n_keep = int(keep.sum())
    written = min(n_keep, max(0, len(out) - offset))
    return out, written, n_keep - written


def _compact_case(rng, m, cap, offset, w, p_keep):
    """Random rows and keep mask, and a buffer holding ``offset`` rows
    with zeros past them (as every frontier buffer does)."""
    rows = rng.randint(1, 2 ** 32, size=(m, w), dtype=np.uint64) \
        .astype(np.uint32)
    keep = rng.rand(m) < p_keep
    out = np.zeros((cap, w), dtype=np.uint32)
    out[:offset] = rng.randint(1, 2 ** 32, size=(offset, w),
                               dtype=np.uint64).astype(np.uint32)
    return rows, keep, out


@pytest.mark.parametrize("m,cap,offset,w,p_keep", [
    (64, 256, 0, 2, 0.5),        # fresh buffer
    (64, 256, 37, 2, 0.5),       # append at an offset
    (64, 256, 192, 1, 1.0),      # the window ends exactly at cap
    (64, 256, 220, 2, 0.7),      # overflow: kept rows past cap dropped
    (64, 256, 256, 1, 0.5),      # full buffer: everything dropped
    (64, 256, 100, 2, 0.0),      # all rows invalid
    (512, 256, 0, 1, 0.9),       # more rows than the buffer holds
    (512, 256, 130, 2, 0.4),     # more rows than the buffer, at an offset
    (1, 32, 31, 2, 1.0),         # one row into the last slot
    (96, 128, 5, 1, 0.05),       # sparse keep, W = 1
], ids=["fresh", "offset", "window-at-cap", "overflow", "full", "none-kept",
        "rows-over-cap", "rows-over-cap-offset", "last-slot", "sparse"])
def test_compact_matches_scatter_reference(m, cap, offset, w, p_keep):
    rng = np.random.RandomState(m * 7919 + cap + offset * 31 + w)
    rows, keep, out = _compact_case(rng, m, cap, offset, w, p_keep)
    want, want_written, want_dropped = _np_compact(rows, keep, out, offset)
    got, written, dropped = dedup.compact(
        jnp.asarray(rows), jnp.asarray(keep), jnp.asarray(out), offset)
    got = np.asarray(got)
    assert int(written) == want_written
    assert int(dropped) == want_dropped
    assert np.array_equal(got, want)
    assert not got[offset + want_written:].any()       # zeros past count


@pytest.mark.parametrize("w", [1, 2])
def test_compact_vmapped_per_lane_offsets(w):
    """Under the lane ``vmap`` each lane appends at its own offset: one
    lane fresh, one mid-buffer, one overflowing, one full."""
    import jax
    m, cap = 48, 128
    offsets = [0, 50, 100, 128]
    rng = np.random.RandomState(w)
    cases = [_compact_case(rng, m, cap, off, w, 0.6) for off in offsets]
    rows, keep, out = (np.stack(c) for c in zip(*cases))
    got, written, dropped = jax.vmap(dedup.compact)(
        jnp.asarray(rows), jnp.asarray(keep), jnp.asarray(out),
        jnp.asarray(offsets, dtype=jnp.int32))
    for i, off in enumerate(offsets):
        want, want_written, want_dropped = _np_compact(
            rows[i], keep[i], out[i], off)
        assert int(written[i]) == want_written
        assert int(dropped[i]) == want_dropped
        assert np.array_equal(np.asarray(got[i]), want)
