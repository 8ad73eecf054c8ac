"""Simplicial-vertex pruning (the paper's §5 proposed rule, implemented
bit-parallel): correctness + branch-collapse reductions."""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset, expand, graph, ladder


def _is_simplicial_oracle(g, s, v):
    """v simplicial in the graph after eliminating S (python oracle)."""
    adjb = [list(map(bool, row)) for row in g.adj]
    q = [u for u in range(g.n) if u not in s and u != v
         and expand.degree_oracle(adjb, s | {u} - {u}, u) >= 0]  # noqa
    # neighbors of v in G_S:
    nbrs = []
    seen = [False] * g.n
    stack = [v]
    seen[v] = True
    while stack:
        u = stack.pop()
        for wv in range(g.n):
            if g.adj[u][wv] and not seen[wv]:
                seen[wv] = True
                if wv in s:
                    stack.append(wv)
                else:
                    nbrs.append(wv)
    # clique check among nbrs in G_S: a,b adjacent iff b reachable from a
    for i, a in enumerate(nbrs):
        reach_a = set()
        seen2 = [False] * g.n
        st = [a]
        seen2[a] = True
        while st:
            u = st.pop()
            for wv in range(g.n):
                if g.adj[u][wv] and not seen2[wv]:
                    seen2[wv] = True
                    if wv in s:
                        st.append(wv)
                    else:
                        reach_a.add(wv)
        for b in nbrs[i + 1:]:
            if b not in reach_a:
                return False
    return True


@pytest.mark.parametrize("seed", range(5))
def test_simplicial_mask_matches_oracle(seed):
    rng = random.Random(seed)
    n = rng.randint(4, 20)
    g = graph.gnp(n, rng.choice([0.2, 0.45]), seed)
    s = set(rng.sample(range(n), rng.randint(0, n // 2)))
    adj = jnp.asarray(g.packed())
    states = jnp.asarray(bitset.np_pack([s], n))
    valid = jnp.asarray([True])
    allowed = bitset.full(n)
    _, feas, _, reach = expand.expand_block(
        adj, states, valid, jnp.int32(n), allowed, n)
    simp = np.asarray(expand.simplicial_mask(adj, states, reach, feas, n))[0]
    for v in range(n):
        if v in s:
            continue
        assert bool(simp[v]) == _is_simplicial_oracle(g, s, v), (v, s)


def test_collapse_keeps_single_candidate():
    feas = jnp.asarray([[True, True, True], [True, False, True]])
    simp = jnp.asarray([[False, True, True], [False, False, False]])
    out = np.asarray(expand.collapse_simplicial(feas, simp))
    assert out.tolist() == [[False, True, False], [True, False, True]]


@pytest.mark.parametrize("name,want", [("petersen", 4), ("myciel3", 5)])
def test_solver_simplicial_correct_and_prunes(name, want):
    g = graph.REGISTRY[name]()
    a = ladder.solve(g, cap=1 << 14, block=1 << 8)
    b = ladder.solve(g, cap=1 << 14, block=1 << 8, use_simplicial=True)
    assert a.width == b.width == want
    assert b.expanded <= a.expanded


def test_tree_collapses_greedily():
    """Trees are chordal-ish: every state has a simplicial leaf, so the
    search degenerates to a single path (massive reduction)."""
    g = graph.random_tree(14, 5)
    b = ladder.solve(g, cap=1 << 12, block=1 << 6, use_simplicial=True,
                     use_preprocess=False, use_paths=False,
                     use_clique=False)
    assert b.width == 1
    # one chain of states per level at k=1: expanded ~ n per level bound
    assert b.expanded <= 3 * g.n
