import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import oracle
from repro.core import bounds, graph, ladder, preprocess, solver

FAST = dict(cap=1 << 16, block=1 << 9)

# the shared golden-widths file (tests/golden_widths.json via tests/oracle.py)
# is the single source of truth for known exact treewidths
KNOWN = oracle.golden_cases()
HEAVY = oracle.golden_widths()


@pytest.mark.parametrize("name,gf,want", KNOWN, ids=[c[0] for c in KNOWN])
def test_known_treewidth(name, gf, want):
    g = gf()
    r = ladder.solve(g, **FAST)
    assert r.exact and r.width == want, (g.name, r)


@pytest.mark.slow
def test_grid5x5_heavy():
    """Grids are state-heavy (cf. the paper's 8x6 torus at 2.1e9 states)."""
    r = ladder.solve(graph.grid(5, 5), cap=1 << 19, block=1 << 11)
    assert r.exact and r.width == HEAVY["grid5x5"]["tw"]


def test_mcgee_overflow_semantics():
    """With a small list cap the run overflows: the found width is still the
    true value here (paper: myciel5 found exactly despite overflow), but the
    result must be flagged inexact."""
    r = ladder.solve(graph.mcgee(), cap=1 << 16, block=1 << 9)
    assert r.width == HEAVY["mcgee"]["tw"] and not r.exact


@pytest.mark.slow
def test_mcgee_exact():
    r = ladder.solve(graph.mcgee(), cap=1 << 22, block=1 << 12)
    assert r.exact and r.width == HEAVY["mcgee"]["tw"]


def test_relabel_invariance():
    rng = np.random.RandomState(3)
    g = graph.queen(5)
    base = ladder.solve(g, **FAST).width
    for _ in range(2):
        perm = rng.permutation(g.n)
        assert ladder.solve(g.relabel(perm), **FAST).width == base


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_property_partial_ktree_bound(seed):
    """Random partial k-trees have tw <= k; solver must respect that."""
    rng = random.Random(seed)
    k = rng.randint(1, 4)
    n = rng.randint(k + 2, 16)
    g = graph.random_partial_ktree(n, k, drop=0.3, seed=seed)
    r = ladder.solve(g, **FAST)
    assert r.exact and r.width <= k


def test_bloom_mode_agrees():
    for name in ["petersen", "myciel3", "queen5_5"]:
        g = graph.REGISTRY.get(name, lambda: graph.petersen())()
        a = ladder.solve(g, mode="sort", **FAST)
        b = ladder.solve(g, mode="bloom", m_bits=1 << 22, **FAST)
        assert a.width == b.width


def test_disconnected_graph():
    # union of a clique and a cycle: tw = max(4, 2)
    a = graph.complete(5)
    b = graph.cycle(6)
    n = a.n + b.n
    adj = np.zeros((n, n), dtype=bool)
    adj[:5, :5] = a.adj
    adj[5:, 5:] = b.adj
    g = graph.Graph(n, adj, "disc")
    r = ladder.solve(g, **FAST)
    assert r.exact and r.width == 4


def test_overflow_marks_inexact():
    g = graph.queen(5)
    r = ladder.solve(g, cap=64, block=32, use_preprocess=False, use_paths=False)
    # tiny capacity must either still find the right answer or mark inexact
    assert (not r.exact) or r.width == 18


def test_reconstruction_order_is_valid():
    g = graph.petersen()
    r = ladder.solve(g, use_preprocess=False, reconstruct=True, **FAST)
    assert r.order is not None and len(r.order) == g.n
    assert sorted(r.order) == list(range(g.n))
    assert solver.order_width(g, r.order) == r.width == 4


def test_reconstruction_queen5():
    g = graph.queen(5)
    r = ladder.solve(g, use_preprocess=False, reconstruct=True, **FAST)
    assert solver.order_width(g, r.order) == 18


def test_preprocess_block_safety():
    """tw computed via block decomposition == tw of the raw graph."""
    rng = random.Random(11)
    for seed in range(3):
        g = graph.gnp(14, 0.25, 50 + seed)
        a = ladder.solve(g, use_preprocess=True, **FAST)
        b = ladder.solve(g, use_preprocess=False, **FAST)
        assert a.width == b.width, (seed, a.width, b.width)


def test_schedules_agree():
    g = graph.myciel(3)
    widths = {s: ladder.solve(g, schedule=s, **FAST).width
              for s in ("doubling", "while", "linear")}
    assert set(widths.values()) == {5}


def test_expanded_counts_deterministic():
    g = graph.petersen()
    a = ladder.solve(g, **FAST)
    b = ladder.solve(g, **FAST)
    assert a.expanded == b.expanded


def test_upper_bound_heuristics():
    g = graph.grid(6, 6)
    ub, order = bounds.upper_bound(g)
    assert ub >= 6
    assert solver.order_width(g, order) == ub
    assert bounds.lower_bound(g) <= 6
