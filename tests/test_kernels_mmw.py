"""Pallas MMW kernel vs the validated core implementation (which is itself
checked against the python contraction oracle in test_core_mmw.py)."""
import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitset, components, graph
from repro.kernels.common import LANES, lane_geometry
from repro.kernels.mmw import mmw_bounds, mmw_bounds_ref


def _case(n, n_states, seed, p=0.3):
    rng = random.Random(seed)
    g = graph.gnp(n, p, seed)
    adj = jnp.asarray(g.packed())
    ss = [set(rng.sample(range(n), rng.randint(0, n // 2)))
          for _ in range(n_states)]
    states = jnp.asarray(bitset.np_pack(ss, n))
    _, reach = jax.vmap(
        lambda s: components.eliminated_degrees(adj, s, n))(states)
    return reach, states


@pytest.mark.parametrize("n", [5, 16, 31, 33, 48, 64])
def test_shape_sweep(n):
    reach, states = _case(n, 6, seed=n)
    got = np.asarray(mmw_bounds(reach, states, jnp.int32(1000), n=n,
                                block=2))
    want = np.asarray(mmw_bounds_ref(reach, states, jnp.int32(1000), n))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("block", [1, 3, 8])
def test_block_sweep_and_padding(block):
    """Three grid steps of ``block`` rows of 128 states, the last padded."""
    n, b = 20, 2 * LANES * block + 37
    rows, step = lane_geometry(b, block)
    assert (rows // step, step) == (3, block)
    reach, states = _case(n, b, seed=3)
    got = np.asarray(mmw_bounds(reach, states, jnp.int32(1000), n=n,
                                block=block))
    want = np.asarray(mmw_bounds_ref(reach, states, jnp.int32(1000), n))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k", [0, 2, 5])
def test_early_freeze_matches_core(k):
    """Both implementations freeze the bound once it exceeds k."""
    n = 24
    reach, states = _case(n, 8, seed=9, p=0.5)
    got = np.asarray(mmw_bounds(reach, states, jnp.int32(k), n=n, block=4))
    want = np.asarray(mmw_bounds_ref(reach, states, jnp.int32(k), n))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("density", [0.05, 0.6, 0.95])
def test_density_sweep(density):
    n = 30
    reach, states = _case(n, 4, seed=11, p=density)
    got = np.asarray(mmw_bounds(reach, states, jnp.int32(1000), n=n,
                                block=4))
    want = np.asarray(mmw_bounds_ref(reach, states, jnp.int32(1000), n))
    assert np.array_equal(got, want)
