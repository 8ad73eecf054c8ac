"""Pallas expansion kernel vs pure-jnp ref vs the paper's DFS oracle."""
import random

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitset, expand, graph
from repro.kernels.common import LANES, lane_geometry
from repro.kernels.expand import expand_degrees, expand_ref


def _random_case(n, n_states, seed, p=0.3):
    rng = random.Random(seed)
    g = graph.gnp(n, p, seed)
    ss = [set(rng.sample(range(n), rng.randint(0, n - 1)))
          for _ in range(n_states)]
    return g, ss


@pytest.mark.parametrize("n", [3, 17, 31, 32, 33, 48, 64, 96])
def test_kernel_matches_ref_shape_sweep(n):
    g, ss = _random_case(n, 6, seed=n)
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack(ss, n))
    got = np.asarray(expand_degrees(adj, states, n=n, block=2))
    want = np.asarray(expand_ref(adj, states, n))
    for b, s in enumerate(ss):
        for v in range(n):
            if v not in s:
                assert got[b, v] == want[b, v]


@pytest.mark.parametrize("block", [1, 2, 8, 16])
def test_block_size_sweep(block):
    """Three grid steps of ``block`` rows of 128 states, the last padded."""
    n, b = 24, 2 * LANES * block + 37
    rows, step = lane_geometry(b, block)
    assert (rows // step, step) == (3, block)
    g, ss = _random_case(n, b, seed=7)
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack(ss, n))
    got = np.asarray(expand_degrees(adj, states, n=n, block=block))
    want = np.asarray(expand_ref(adj, states, n))
    mask = ~np.asarray([[v in s for v in range(n)] for s in ss])
    assert np.array_equal(got[mask], want[mask])


def test_kernel_matches_dfs_oracle():
    n = 20
    g, ss = _random_case(n, 5, seed=3, p=0.4)
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack(ss, n))
    got = np.asarray(expand_degrees(adj, states, n=n, block=5))
    adjb = [list(map(bool, row)) for row in g.adj]
    for b, s in enumerate(ss):
        for v in range(n):
            if v not in s:
                assert got[b, v] == expand.degree_oracle(adjb, s, v)


def test_padding_is_stripped():
    n = 10
    g, ss = _random_case(n, 3, seed=5)
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack(ss, n))
    out = expand_degrees(adj, states, n=n, block=16)   # 3 -> padded to 16
    assert out.shape == (3, n)


@pytest.mark.parametrize("density", [0.05, 0.5, 0.95])
def test_density_sweep(density):
    n = 40
    g, ss = _random_case(n, 4, seed=11, p=density)
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack(ss, n))
    got = np.asarray(expand_degrees(adj, states, n=n, block=4))
    want = np.asarray(expand_ref(adj, states, n))
    mask = ~np.asarray([[v in s for v in range(n)] for s in ss])
    assert np.array_equal(got[mask], want[mask])
