"""Multi-lane engine parity + regression tests for the solver bugfixes.

The batched engine must be a pure scheduling transform: every lane's
verdict, inexactness and expansion count is pinned bit-for-bit to the
sequential ``decide``/``solve`` loop it replaces, across the backend ×
dedup mode × pruning matrix (pallas runs in interpret mode on CPU).  The
suite driver must additionally do it in *fewer* dispatches — that is the
acceptance criterion, asserted here via ``engine.COUNTERS``.

Also pins the two user-facing bugfixes that ride along:
  * ``solve(reconstruct=True, use_preprocess=True)`` used to silently
    return ``order=None`` (the preprocess loop hardcoded
    ``reconstruct=False``);
  * a block with ``start_k >= ub`` used to overwrite the genuine lower
    bound and report ``exact=True`` with zero search.
"""
import warnings

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import backend as backend_lib
from repro.core import batch, engine, graph, ladder, preprocess, solver

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)

CONFIGS = [
    dict(mode="sort", use_mmw=False, use_simplicial=False),
    dict(mode="bloom", use_mmw=False, use_simplicial=False),
    dict(mode="sort", use_mmw=True, use_simplicial=False),
    dict(mode="sort", use_mmw=False, use_simplicial=True),
]
CONFIG_IDS = ["sort", "bloom", "sort+mmw", "sort+simplicial"]

DECIDE_KW = dict(cap=1 << 10, block=BLOCK, m_bits=1 << 12, k_hashes=4,
                 schedule="doubling")


# ------------------------------------------------- speculative k-windows

@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("cfg", CONFIGS, ids=CONFIG_IDS)
def test_decide_batch_matches_sequential_decide(cfg, backend):
    """Speculative lanes are bit-identical to the sequential k-ladder for
    every backend x mode x pruning combo (lanes share the true n, so no
    padding caveats apply)."""
    g = graph.petersen()
    ks = list(range(2, 6))
    lanes = batch.decide_lanes([batch.Lane(g, k) for k in ks],
                               backend=backend, **DECIDE_KW, **cfg)
    for k, lane in zip(ks, lanes):
        ref = solver.decide(g, k, [], engine="fused", backend=backend,
                            **DECIDE_KW, **cfg)
        assert (lane.feasible, lane.inexact, lane.expanded) == \
            (ref.feasible, ref.inexact, ref.expanded), (backend, cfg, k)


@given(st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_decide_batch_random_graphs_with_clique(seed):
    """Random graphs, random k-windows, a clique skip set, and a cap small
    enough that overflow accounting is exercised per lane."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(8, 13))
    g = graph.gnp(n, float(rng.uniform(0.2, 0.55)), seed)
    from repro.core import bounds
    clique = bounds.greedy_max_clique(g)
    k0 = int(rng.randint(1, max(2, n - 3)))
    ks = list(range(k0, min(k0 + 4, n - 1)))
    if not ks:
        return
    kw = dict(cap=512, block=BLOCK, m_bits=1 << 12, k_hashes=4,
              schedule="doubling", mode="sort", use_mmw=False,
              use_simplicial=False)
    lanes = batch.decide_lanes([batch.Lane(g, k, tuple(clique)) for k in ks],
                               **kw)
    for k, lane in zip(ks, lanes):
        ref = solver.decide(g, k, clique, engine="fused", **kw)
        assert (lane.feasible, lane.inexact, lane.expanded) == \
            (ref.feasible, ref.inexact, ref.expanded), (seed, k)


def test_decide_lanes_cross_n_padding():
    """Lanes of different true n padded to a common n_max: verdicts and
    expansion counts still match the unpadded sequential runs (sort mode:
    zero-padded words keep the dedup order bit-identical)."""
    gs = [graph.petersen(), graph.myciel(3), graph.grid(3, 4)]
    lanes = [batch.Lane(g, k) for g in gs for k in (2, 4)]
    kw = dict(cap=512, block=BLOCK, mode="sort", use_mmw=False,
              m_bits=1 << 12, k_hashes=4, schedule="doubling")
    out = batch.decide_lanes(lanes, n_pad=32, lane_pad=8, **kw)
    assert len(out) == len(lanes)
    for lane, res in zip(lanes, out):
        ref = solver.decide(lane.g, lane.k, [], engine="fused", **kw)
        assert (res.feasible, res.inexact, res.expanded) == \
            (ref.feasible, ref.inexact, ref.expanded), (lane.g.name, lane.k)


@pytest.mark.parametrize("k0,cap", [(4, 1 << 12), (5, 1 << 16)],
                         ids=["desargues-drops", "no-drops"])
def test_lanes_refill_at_different_levels_bit_identical(k0, cap):
    """Lanes whose buffers fill at different levels, or not at all, under
    one lane-uniform refill: each lane's frontier, counts and drops are
    bit-identical to its single-lane run, though the pool refills a lane
    whenever another lane needs it.  At the smaller cap the first lane's
    widest levels overflow, so the identity holds with drops too."""
    from repro.core import frontier
    lanes = [batch.Lane(graph.REGISTRY["desargues"](), k0),
             batch.Lane(graph.REGISTRY["desargues"](), k0 - 1),
             batch.Lane(graph.petersen(), 4), batch.Lane(graph.myciel(3), 5)]
    n_pad, w = 32, 1
    kw = dict(n=n_pad, cap=cap, block=BLOCK, mode="sort", use_mmw=False,
              m_bits=1, k_hashes=1, schedule="doubling", backend="jax",
              use_simplicial=False)

    def args(ls):
        adj, allowed, ks, targets = batch._pack_lanes(ls, n_pad, w)
        return (adj, allowed, ks, targets,
                frontier.lane_frontiers(len(ls), cap, w))

    fr, lev, exp, drop, ref, app = batch._lanes_decide(*args(lanes), **kw)
    alone = [batch._lanes_decide(*args([lane]), **kw) for lane in lanes]
    for i, (fr1, lev1, exp1, drop1, ref1, app1) in enumerate(alone):
        assert (int(lev[i]), int(exp[i]), int(drop[i]), int(app[i])) == \
            (int(lev1[0]), int(exp1[0]), int(drop1[0]), int(app1[0])), i
        assert int(fr.count[i]) == int(fr1.count[0])
        np.testing.assert_array_equal(np.asarray(fr.states[i]),
                                      np.asarray(fr1.states[0]))
        assert int(ref[i]) >= int(ref1[0])
    alone_refills = [int(a[4][0]) for a in alone]
    assert alone_refills[0] > 0 and alone_refills[2:] == [0, 0]
    assert (int(drop[0]) > 0) == (cap < 1 << 15)
    assert int(drop[1]) == 0


def test_decide_lanes_trivial_target_matches_decide_early_return():
    """k+1 >= n lanes are trivially feasible with zero expansion, exactly
    like solver.decide's target<=0 early return."""
    g = graph.petersen()
    out = batch.decide_lanes([batch.Lane(g, g.n - 1), batch.Lane(g, 3)],
                             cap=256, block=BLOCK, mode="sort",
                             use_mmw=False, m_bits=1, k_hashes=1,
                             schedule="doubling")
    ref = solver.decide(g, g.n - 1, [], engine="fused", cap=256,
                        block=BLOCK, mode="sort", use_mmw=False, m_bits=1,
                        k_hashes=1, schedule="doubling")
    assert (out[0].feasible, out[0].inexact, out[0].expanded) == \
        (ref.feasible, ref.inexact, ref.expanded) == (True, False, 0)


def test_single_lane_decide_copies_counts_only():
    """A one-lane rung (``solver.decide``'s device path) keeps its
    frontier on device: the handle's arrays hold per-lane counts only,
    no ``(cap, W)`` buffer, so its one host sync copies a few ints."""
    g = graph.myciel(3)
    cap = 1 << 10
    h = batch.decide_lanes_async([batch.Lane(g, 4)], cap=cap, block=BLOCK,
                                 mode="sort", use_mmw=False, m_bits=1,
                                 k_hashes=1, schedule="doubling")
    import jax
    leaves = jax.tree_util.tree_leaves(h.arrays)
    assert leaves and all(leaf.ndim == 1 and leaf.shape[0] == 1
                          for leaf in leaves), [x.shape for x in leaves]
    assert sum(leaf.size for leaf in leaves) < cap
    [res] = h.result()
    ref = solver.decide(g, 4, [], engine="host", cap=cap, block=BLOCK,
                        mode="sort", use_mmw=False, m_bits=1, k_hashes=1,
                        schedule="doubling")
    assert (res.feasible, res.inexact, res.expanded) == \
        (ref.feasible, ref.inexact, ref.expanded)


def test_lanes_capability_validation():
    with pytest.raises(backend_lib.BackendCapabilityError):
        backend_lib.validate("jax", lanes=0)
    with pytest.raises(backend_lib.BackendCapabilityError):
        ladder.solve(graph.petersen(), lanes=0, **FAST)
    # both shipped backends are vmap-safe; a non-member must be rejected
    # before tracing
    old = backend_lib.BATCHED_BACKENDS
    backend_lib.BATCHED_BACKENDS = ("jax",)
    try:
        with pytest.raises(backend_lib.BackendCapabilityError):
            backend_lib.validate("pallas", lanes=2)
    finally:
        backend_lib.BATCHED_BACKENDS = old


# ------------------------------------------------------------- solve lanes

def test_solve_speculative_lanes_agreement():
    """solve(lanes=L) is bit-identical to solve() in result AND ladder
    accounting, for several L."""
    for g in [graph.petersen(), graph.myciel(3), graph.gnp(12, 0.35, 3)]:
        ref = ladder.solve(g, **FAST)
        for lanes in (2, 3, 8):
            got = ladder.solve(g, lanes=lanes, **FAST)
            assert (got.width, got.exact, got.expanded, got.lb, got.ub,
                    got.per_k) == \
                (ref.width, ref.exact, ref.expanded, ref.lb, ref.ub,
                 ref.per_k), (g.name, lanes)


@pytest.mark.parametrize("lanes", [1, 3])
def test_solve_is_the_round_loop_of_one_instance(lanes):
    """solve(g, lanes=L) and solve_many([g], speculate=L) run the same
    round loop: same width, bounds, expanded and per-rung accounting, for
    a graph whose preprocessing leaves several blocks."""
    a, b = graph.myciel(3), graph.petersen()       # joined at a cut vertex
    edges = [(int(u), int(v)) for u, v in zip(*np.nonzero(np.triu(a.adj)))]
    edges += [(int(u) + a.n - 1, int(v) + a.n - 1)
              for u, v in zip(*np.nonzero(np.triu(b.adj)))]
    g = graph.from_edges(a.n + b.n - 1, edges, name="myciel3+petersen")
    one = ladder.solve(g, lanes=lanes, **FAST)
    [many] = ladder.solve_many([g], speculate=lanes, **FAST)
    assert (one.width, one.exact, one.lb, one.ub, one.expanded,
            one.per_k) == (many.width, many.exact, many.lb, many.ub,
                           many.expanded, many.per_k)
    assert len(one.per_k) == 2 and all(one.per_k.values())


def test_solve_speculative_fewer_dispatches():
    """Speculation's point: the myciel4 ladder (k=6..10 after bounds) runs
    in fewer dispatches at lanes=4 than sequentially."""
    g = graph.myciel(4)
    engine.reset_counters()
    ref = ladder.solve(g, **FAST)
    seq = dict(engine.COUNTERS)
    engine.reset_counters()
    got = ladder.solve(g, lanes=4, **FAST)
    bat = dict(engine.COUNTERS)
    assert (got.width, got.exact, got.expanded) == \
        (ref.width, ref.exact, ref.expanded)
    assert bat["dispatches"] < seq["dispatches"]
    assert bat["host_syncs"] < seq["host_syncs"]


# -------------------------------------------------------------- solve_many

SUITE = ["petersen", "myciel3", "queen5_5", "desargues"]


def _suite_graphs():
    return [graph.REGISTRY[k]() for k in SUITE]


def test_solve_many_matches_sequential_solve_with_fewer_dispatches():
    """The acceptance criterion: identical widths/exactness (and here the
    full result surface) to sequential solve, in fewer total dispatches."""
    gs = _suite_graphs()
    engine.reset_counters()
    seq = [ladder.solve(g, **FAST) for g in gs]
    seq_c = dict(engine.COUNTERS)
    engine.reset_counters()
    man = ladder.solve_many(gs, **FAST)
    bat_c = dict(engine.COUNTERS)
    for g, a, b in zip(gs, seq, man):
        assert (a.width, a.exact, a.expanded, a.lb, a.ub, a.per_k) == \
            (b.width, b.exact, b.expanded, b.lb, b.ub, b.per_k), g.name
    assert bat_c["dispatches"] < seq_c["dispatches"]
    assert bat_c["host_syncs"] < seq_c["host_syncs"]


@pytest.mark.parametrize("backend,mode", [("jax", "sort"), ("jax", "bloom"),
                                          ("pallas", "sort")])
def test_solve_many_backend_mode_matrix(backend, mode):
    """Width/exactness parity per backend x mode.  bloom keeps every lane
    at one shared W here (all suite members are < 32 vertices), so even
    the hash-sensitive mode stays bit-identical."""
    gs = [graph.petersen(), graph.myciel(3), graph.desargues()]
    kw = dict(cap=1 << 12, block=BLOCK, mode=mode, backend=backend,
              m_bits=1 << 14, schedule="doubling")
    seq = [ladder.solve(g, **kw) for g in gs]
    man = ladder.solve_many(gs, **kw)
    for g, a, b in zip(gs, seq, man):
        assert (a.width, a.exact, a.expanded) == \
            (b.width, b.exact, b.expanded), (g.name, backend, mode)


def test_solve_many_pruning_rules_verdict_parity():
    """MMW/simplicial enabled: padded lanes may expand a superset (the
    padding-weakened-MMW caveat) but widths and exactness must match."""
    gs = [graph.petersen(), graph.myciel(3)]
    kw = dict(cap=1 << 12, block=BLOCK, use_mmw=True, use_simplicial=True)
    seq = [ladder.solve(g, **kw) for g in gs]
    man = ladder.solve_many(gs, **kw)
    for g, a, b in zip(gs, seq, man):
        assert (a.width, a.exact) == (b.width, b.exact), g.name
        assert b.expanded >= a.expanded, g.name


def test_solve_many_edge_instances():
    """Empty / single-vertex / disconnected inputs keep solve()'s shapes."""
    import numpy as _np
    empty = graph.Graph(0, _np.zeros((0, 0), dtype=bool), "empty")
    single = graph.Graph(1, _np.zeros((1, 1), dtype=bool), "single")
    disc_adj = _np.zeros((11, 11), dtype=bool)
    disc_adj[:5, :5] = graph.complete(5).adj
    disc_adj[5:, 5:] = graph.cycle(6).adj
    disc = graph.Graph(11, disc_adj, "disc")
    gs = [empty, single, disc, graph.petersen()]
    seq = [ladder.solve(g, **FAST) for g in gs]
    man = ladder.solve_many(gs, **FAST)
    for g, a, b in zip(gs, seq, man):
        assert (a.width, a.exact, a.expanded, a.per_k) == \
            (b.width, b.exact, b.expanded, b.per_k), g.name


def test_solve_many_no_preprocess_and_speculate():
    gs = [graph.petersen(), graph.gnp(12, 0.3, 11)]
    seq = [ladder.solve(g, use_preprocess=False, **FAST) for g in gs]
    for spec in (1, 3):
        man = ladder.solve_many(gs, use_preprocess=False, speculate=spec,
                                **FAST)
        for g, a, b in zip(gs, seq, man):
            assert (a.width, a.exact, a.expanded, a.lb, a.ub, a.per_k) == \
                (b.width, b.exact, b.expanded, b.lb, b.ub, b.per_k), \
                (g.name, spec)


# ------------------------------------------- bugfix 1: reconstruct + pre

def _articulated_graph():
    """Two K5s sharing an articulation vertex, a bridge, a pendant path:
    exercises top-level reduction, block splitting, empty bridge blocks
    and per-block reduction in one instance."""
    adj = np.zeros((12, 12), dtype=bool)
    for u in range(5):
        for v in range(u + 1, 5):
            adj[u, v] = adj[v, u] = True
    for u in range(4, 9):
        for v in range(u + 1, 9):
            adj[u, v] = adj[v, u] = True
    adj[8, 9] = adj[9, 8] = True
    adj[9, 10] = adj[10, 9] = True
    adj[10, 11] = adj[11, 10] = True
    return graph.Graph(12, adj, "barbell")


def test_reconstruct_with_preprocess_returns_certified_order():
    """Regression: used to silently return order=None (preprocess loop
    hardcoded reconstruct=False)."""
    for g in [graph.petersen(), _articulated_graph(), graph.grid(3, 5),
              graph.gnp(14, 0.25, 51)]:
        r = ladder.solve(g, reconstruct=True, use_preprocess=True, **FAST)
        assert r.order is not None, g.name
        assert sorted(r.order) == list(range(g.n)), g.name
        assert solver.order_width(g, r.order) <= r.width, g.name
        if r.exact:
            assert solver.order_width(g, r.order) == r.width, g.name


@given(st.integers(0, 5000))
@settings(max_examples=8, deadline=None)
def test_reconstruct_preprocess_property(seed):
    """Random sparse graphs (rich articulation structure): stitched order
    is a permutation certifying the computed width."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(6, 15))
    g = graph.gnp(n, float(rng.uniform(0.12, 0.3)), seed)
    r = ladder.solve(g, reconstruct=True, use_preprocess=True, **FAST)
    assert r.order is not None and sorted(r.order) == list(range(n))
    assert solver.order_width(g, r.order) <= r.width


def test_stitch_block_orders_handles_empty_bridge_blocks():
    """A bridge block fully reduces away; its endpoints must still land in
    the stitched order via the block-cut forest (the old code dropped
    empty blocks entirely)."""
    g = _articulated_graph()
    pre = preprocess.preprocess(g)
    covered = set(pre.removed)
    for b in pre.blocks:
        covered.update(b.vertices)
    assert covered == set(range(g.n))
    order = preprocess.stitch_block_orders(
        pre, [list(range(b.g.n)) for b in pre.blocks])
    assert sorted(order) == list(range(g.n))


def test_reconstruction_agrees_with_and_without_preprocess():
    g = graph.queen(5)
    a = ladder.solve(g, reconstruct=True, use_preprocess=False, **FAST)
    b = ladder.solve(g, reconstruct=True, use_preprocess=True, **FAST)
    assert a.width == b.width == 18
    assert solver.order_width(g, a.order) == 18
    assert solver.order_width(g, b.order) == 18


# --------------------------------------------------- bugfix 2: start_k

def test_start_k_at_or_above_ub_is_not_exact():
    """Regression: start_k >= ub used to hit the lb >= ub early return and
    claim exact=True with zero search."""
    g = graph.petersen()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = ladder.solve(g, use_preprocess=False, start_k=50, **FAST)
    assert r.expanded == 0
    assert not r.exact                       # nothing was proven
    assert r.width == r.ub                   # heuristic ub passed through
    assert r.lb <= 4                         # genuine bound, not start_k
    assert any("start_k" in str(x.message) for x in w)


def test_start_k_forced_above_lb_feasible_immediately_is_inexact():
    """tw(petersen)=4: starting at 4 finds it feasible at once, but
    nothing proved tw > 3, so exact must be False."""
    g = graph.petersen()
    r = ladder.solve(g, use_preprocess=False, start_k=4, **FAST)
    assert r.width == 4 and not r.exact


def test_start_k_forced_but_ladder_proves_exactness():
    """Starting above lb but below tw: the infeasible rung below the
    answer restores the proof, so exact stays True."""
    g = graph.torus_grid(4, 4)   # genuine lb 4 < tw 6
    ref = ladder.solve(g, use_preprocess=False, **FAST)
    assert ref.exact and ref.width == 6 and ref.lb == 4
    r = ladder.solve(g, use_preprocess=False, start_k=5, **FAST)
    assert r.width == 6 and r.exact
    assert r.lb == 4             # reported lb is the genuine bound


def test_start_k_below_lb_keeps_exactness():
    g = graph.petersen()
    r = ladder.solve(g, use_preprocess=False, start_k=1, **FAST)
    assert r.width == 4 and r.exact


def test_start_k_speculative_lanes_agree():
    g = graph.petersen()
    for sk in (1, 4, 50):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            a = ladder.solve(g, use_preprocess=False, start_k=sk, **FAST)
            b = ladder.solve(g, use_preprocess=False, start_k=sk, lanes=4,
                             **FAST)
        assert (a.width, a.exact, a.expanded, a.lb, a.ub) == \
            (b.width, b.exact, b.expanded, b.lb, b.ub), sk
