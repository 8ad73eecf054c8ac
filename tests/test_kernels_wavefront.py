"""Fused wavefront kernel vs the jax backend composition vs the DFS oracle.

The kernel's contract is bit-for-bit equality with
``repro.core.expand.wavefront_expand`` (the registered jax implementation)
for every pruning-flag combination — that is what makes ``backend="pallas"``
a pure performance transform of ``backend="jax"``.
"""
import random

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitset, expand, graph
from repro.kernels.common import LANES, lane_geometry
from repro.kernels.wavefront import wavefront_expand, wavefront_ref


def _case(n, n_states, seed, p=0.3):
    rng = random.Random(seed)
    g = graph.gnp(n, p, seed)
    ss = [set(rng.sample(range(n), rng.randint(0, max(0, n // 2))))
          for _ in range(n_states)]
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack(ss, n))
    valid = jnp.ones((n_states,), dtype=bool)
    allowed = bitset.full(n)
    return g, ss, adj, states, valid, allowed


def _both(adj, states, valid, k, allowed, n, **flags):
    got = wavefront_expand(adj, states, valid, jnp.int32(k), allowed,
                           n=n, block=2, **flags)
    want = wavefront_ref(adj, states, valid, jnp.int32(k), allowed,
                         n=n, **flags)
    return [np.asarray(x) for x in got], [np.asarray(x) for x in want]


@pytest.mark.parametrize("n", [3, 17, 31, 32, 33, 48])
def test_matches_ref_shape_sweep(n):
    _, _, adj, states, valid, allowed = _case(n, 6, seed=n)
    (gc, gf), (wc, wf) = _both(adj, states, valid, n // 2, allowed, n)
    np.testing.assert_array_equal(gc, wc)
    np.testing.assert_array_equal(gf, wf)


@pytest.mark.parametrize("use_mmw,use_simplicial",
                         [(True, False), (False, True), (True, True)])
def test_pruning_flags_match_ref(use_mmw, use_simplicial):
    n = 20
    _, _, adj, states, valid, allowed = _case(n, 8, seed=5, p=0.35)
    for k in (2, 4, 8):
        (gc, gf), (wc, wf) = _both(adj, states, valid, k, allowed, n,
                                   use_mmw=use_mmw,
                                   use_simplicial=use_simplicial)
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gf, wf)


@pytest.mark.parametrize("block", [1, 2, 8])
def test_block_sweep_and_padding(block):
    """``block`` is rows of 128 states per grid step: the batch spans three
    steps, the last one padded with a partial row and empty rows."""
    n, b = 16, 2 * LANES * block + 37
    rows, step = lane_geometry(b, block)
    assert (rows // step, step) == (3, block)
    _, _, adj, states, valid, allowed = _case(n, b, seed=7)
    got = wavefront_expand(adj, states, valid, jnp.int32(4), allowed,
                           n=n, block=block)
    want = wavefront_ref(adj, states, valid, jnp.int32(4), allowed, n=n)
    assert got[0].shape == (b, n, bitset.n_words(n))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_feasibility_matches_dfs_oracle():
    n = 14
    g, ss, adj, states, valid, allowed = _case(n, 5, seed=3, p=0.4)
    k = 4
    _, feas = wavefront_expand(adj, states, valid, jnp.int32(k), allowed,
                               n=n, block=5)
    feas = np.asarray(feas)
    adjb = [list(map(bool, row)) for row in g.adj]
    for b, s in enumerate(ss):
        for v in range(n):
            want = (v not in s) and expand.degree_oracle(adjb, s, v) <= k
            assert bool(feas[b, v]) == want, (b, v, s)


def test_invalid_rows_are_infeasible():
    n = 12
    _, _, adj, states, _, allowed = _case(n, 4, seed=9)
    valid = jnp.asarray([True, False, True, False])
    _, feas = wavefront_expand(adj, states, valid, jnp.int32(6), allowed,
                               n=n, block=2)
    feas = np.asarray(feas)
    assert not feas[1].any() and not feas[3].any()
    assert feas[0].any() or feas[2].any()


def test_non_doubling_schedule_rejected():
    n = 8
    _, _, adj, states, valid, allowed = _case(n, 2, seed=1)
    with pytest.raises(ValueError, match="doubling"):
        wavefront_expand(adj, states, valid, jnp.int32(3), allowed,
                         n=n, schedule="while")
