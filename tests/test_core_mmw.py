import random

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitset, components, graph, ladder, mmw


def _jax_mmw(g, s, k=1000):
    adj = jnp.asarray(g.packed())
    sw = jnp.asarray(bitset.np_pack([s], g.n)[0])
    _, reach = components.eliminated_degrees(adj, sw, g.n)
    return int(mmw.mmw_bound(reach, sw, jnp.int32(k), g.n))


@pytest.mark.parametrize("seed", range(6))
def test_matches_oracle(seed):
    rng = random.Random(seed)
    n = rng.randint(3, 24)
    g = graph.gnp(n, rng.choice([0.15, 0.3, 0.5]), seed)
    s = set(rng.sample(range(n), rng.randint(0, n // 2)))
    got = _jax_mmw(g, s)
    want = mmw.mmw_oracle(g.adj, s)
    assert got == want, (seed, n, s, got, want)


def test_known_graphs():
    assert _jax_mmw(graph.complete(6), set()) == 5
    assert _jax_mmw(graph.cycle(8), set()) == 2
    assert _jax_mmw(graph.path(8), set()) == 1


@pytest.mark.parametrize("seed", range(5))
def test_mmw_is_lower_bound(seed):
    """MMW(G) <= tw(G): the heuristic must never prune a true solution."""
    rng = random.Random(100 + seed)
    n = rng.randint(4, 14)
    g = graph.gnp(n, 0.4, seed)
    lb = _jax_mmw(g, set())
    tw = ladder.solve(g, cap=1 << 12, block=1 << 6).width
    assert lb <= tw, (g.name, lb, tw)


def test_early_exit_prunes():
    # with tiny k the while loop exits as soon as lb > k; bound still valid
    g = graph.complete(8)
    got = _jax_mmw(g, set(), k=2)
    assert got >= 3   # early exit: >k, not necessarily the full bound


def test_solver_mmw_equivalent_results():
    for name in ["petersen", "mcgee", "grid6x6"]:
        g = graph.REGISTRY[name]()
        a = ladder.solve(g, cap=1 << 14, block=1 << 8, use_mmw=False)
        b = ladder.solve(g, cap=1 << 14, block=1 << 8, use_mmw=True)
        assert a.width == b.width
        assert b.expanded <= a.expanded   # MMW can only prune
