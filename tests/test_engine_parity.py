"""Engine and backend parity: host loop vs lane program, jax vs pallas.

The device program (one lane of ``batch._lanes_decide``, the compiled
``engine.decide_loop``) must be a pure performance transform of the host
reference loop (``solver.run_level``): same frontiers, same verdicts, same
drop accounting, bit for bit.  Both are driven with the same pinned
``block`` so their chunk partitioning — and therefore their dedup and
overflow behaviour — is identical; any divergence is a bug in the
while_loop fusion, not legitimate nondeterminism.

The same contract holds across the backend axis: the fused pallas
wavefront kernel dispatched by ``backend="pallas"`` must reproduce the jax
reference composition exactly, for every engine × dedup mode × pruning
flag — pinned here as a backend × engine matrix on interpret-mode pallas,
with registry capability errors for the combinations that are genuinely
unsupported.

Also pins the engine's contract: O(1) dispatches/host syncs per decide, and
end-to-end ``solve`` agreement with a pure-python Held-Karp treewidth
oracle on random graphs.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

import oracle
from repro.core import backend as backend_lib
from repro.core import batch, bitset, engine, frontier as frontier_lib
from repro.core import graph, ladder, solver

BLOCK = 32          # pinned: host run_level adapts within [32, block], so 32
                    # forces identical chunking in both engines

CONFIGS = [
    dict(mode="sort", use_mmw=False, use_simplicial=False),
    dict(mode="bloom", use_mmw=False, use_simplicial=False),
    dict(mode="sort", use_mmw=True, use_simplicial=False),
    dict(mode="sort", use_mmw=False, use_simplicial=True),
]
CONFIG_IDS = ["sort", "bloom", "sort+mmw", "sort+simplicial"]


def _devify(g):
    adj = jnp.asarray(g.packed())
    allowed = jnp.asarray(np.asarray(bitset.full(g.n)))
    return adj, allowed


def _one_lane(adj, allowed, k, target, *, cap, fr=None, **kw):
    """One lane of the lane program (``batch._lanes_decide``), run for up
    to ``target`` levels from ``fr`` (default the DP root).  Returns
    (feasible, inexact, expanded, frontier, refills, appended), the
    frontier without its lane axis and with the rung's dropped total."""
    if fr is None:
        fr = frontier_lib.empty_frontier(cap, adj.shape[-1])
    out, _lvl, exp, drop, ref, app = batch._lanes_decide(
        adj[None], allowed[None], jnp.asarray([k], jnp.int32),
        jnp.asarray([target], jnp.int32),
        frontier_lib.Frontier(fr.states[None], jnp.asarray(fr.count)[None],
                              jnp.asarray(fr.dropped)[None]),
        cap=cap, **kw)
    fr1 = frontier_lib.Frontier(out.states[0], out.count[0], drop[0])
    return (int(fr1.count) > 0, int(drop[0]) > 0, int(exp[0]), fr1,
            int(ref[0]), int(app[0]))


def _host_levels(adj, allowed, k, levels, *, n, cap, **kw):
    """Drive solver.run_level like decide's host loop; return the final
    frontier plus accumulated (expanded, dropped)."""
    w = adj.shape[-1]
    fr = frontier_lib.empty_frontier(cap, w)
    expanded = dropped = 0
    for _ in range(levels):
        fr, stats = solver.run_level(adj, fr, k, allowed, n=n, cap=cap,
                                     block=BLOCK, **kw)
        expanded += stats.expanded
        dropped += stats.dropped
        if int(fr.count) == 0:
            break
    return fr, expanded, dropped


@pytest.mark.parametrize("cfg", CONFIGS, ids=CONFIG_IDS)
@given(st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_frontier_parity_random_graphs(cfg, seed):
    """Level-by-level frontier buffers match bit for bit (incl. overflow:
    cap=512 is small enough that denser draws drop states)."""
    rng = np.random.RandomState(seed)
    n, cap = 12, 512
    g = graph.gnp(n, float(rng.uniform(0.15, 0.55)), seed)
    k = int(rng.randint(1, n - 2))
    target = n - (k + 1)
    if target <= 0:
        return
    adj, allowed = _devify(g)
    kw = dict(n=n, cap=cap, m_bits=1 << 12, k_hashes=4,
              schedule="doubling", backend="jax", **cfg)

    fr_h, exp_h, drop_h = _host_levels(adj, allowed, k, target, **kw)
    feas_f, inexact_f, exp_f, fr_f, _r, _a = _one_lane(
        adj, allowed, k, target, block=BLOCK, **kw)

    assert exp_f == exp_h
    assert inexact_f == (drop_h > 0)
    assert int(fr_f.dropped) == int(drop_h)
    assert int(fr_f.count) == int(fr_h.count)
    assert feas_f == (int(fr_h.count) > 0)
    np.testing.assert_array_equal(np.asarray(fr_f.states),
                                  np.asarray(fr_h.states))


@pytest.mark.parametrize("cfg", CONFIGS, ids=CONFIG_IDS)
def test_decide_parity_named_graphs(cfg):
    """decide() verdicts agree engine-to-engine across k on real instances."""
    for g in [graph.petersen(), graph.myciel(3)]:
        for k in range(1, 7):
            kw = dict(cap=1 << 12, block=BLOCK, m_bits=1 << 14, k_hashes=4,
                      schedule="doubling", **cfg)
            a = solver.decide(g, k, [], engine="host", **kw)
            b = solver.decide(g, k, [], engine="fused", **kw)
            assert (a.feasible, a.inexact, a.expanded) == \
                (b.feasible, b.inexact, b.expanded), (g.name, k, a, b)


def test_fused_decide_is_one_dispatch_one_sync():
    """The acceptance criterion: O(1) host transfers per k, independent of
    the number of levels and chunks."""
    g = graph.queen(5)          # 18 levels of chunked expansion per decide
    engine.reset_counters()
    solver.decide(g, 17, [], cap=1 << 14, block=BLOCK, mode="sort",
                  use_mmw=False, m_bits=1, k_hashes=1,
                  schedule="doubling", engine="fused")
    assert engine.COUNTERS["dispatches"] == 1
    assert engine.COUNTERS["host_syncs"] == 1

    engine.reset_counters()
    solver.decide(g, 17, [], cap=1 << 14, block=BLOCK, mode="sort",
                  use_mmw=False, m_bits=1, k_hashes=1,
                  schedule="doubling", engine="host")
    # host loop: a dispatch per chunk and several syncs per level — both
    # grow with the instance instead of staying O(1)
    assert engine.COUNTERS["dispatches"] > 10
    assert engine.COUNTERS["host_syncs"] > 10


# ---------------------------------------------------- backend x engine matrix

BACKENDS = ["jax", "pallas"]


@pytest.mark.parametrize("mode", ["sort", "bloom"])
@pytest.mark.parametrize("eng", ["host", "fused"])
def test_backend_engine_matrix_decide_parity(eng, mode):
    """jax vs pallas (interpret mode), per engine and dedup mode, with both
    pruning rules enabled: identical verdict / inexact / expanded across k."""
    g = graph.petersen()
    results = {}
    for backend in BACKENDS:
        kw = dict(cap=1 << 10, block=BLOCK, mode=mode, m_bits=1 << 12,
                  k_hashes=4, schedule="doubling", use_mmw=True,
                  use_simplicial=True, backend=backend)
        results[backend] = [solver.decide(g, k, [], engine=eng, **kw)
                            for k in range(2, 6)]
    for k, (a, b) in enumerate(zip(results["jax"], results["pallas"])):
        assert (a.feasible, a.inexact, a.expanded) == \
            (b.feasible, b.inexact, b.expanded), (eng, mode, k + 2, a, b)


@pytest.mark.parametrize("cfg", CONFIGS, ids=CONFIG_IDS)
def test_backend_frontier_bit_parity(cfg):
    """Final frontier buffers identical between backends, per dedup/prune
    config — the fused pallas kernel is a pure performance transform."""
    for seed in (0, 1):
        n, cap = 10, 256
        g = graph.gnp(n, 0.35, seed)
        k = 3
        target = n - (k + 1)
        adj, allowed = _devify(g)
        out = {}
        for backend in BACKENDS:
            out[backend] = _one_lane(
                adj, allowed, k, target, n=n, cap=cap, block=BLOCK,
                m_bits=1 << 12, k_hashes=4, schedule="doubling",
                backend=backend, **cfg)[:4]
        (feas_j, inex_j, exp_j, fr_j) = out["jax"]
        (feas_p, inex_p, exp_p, fr_p) = out["pallas"]
        assert (feas_j, inex_j, exp_j) == (feas_p, inex_p, exp_p)
        assert int(fr_j.count) == int(fr_p.count)
        assert int(fr_j.dropped) == int(fr_p.dropped)
        np.testing.assert_array_equal(np.asarray(fr_j.states),
                                      np.asarray(fr_p.states))


def test_unsupported_backend_combos_fail_at_dispatch():
    """The registry rejects genuinely unsupported combos with a capability
    error at entry — not a TypeError mid-jit (the old impl= failure mode)."""
    g = graph.petersen()
    kw = dict(cap=1 << 8, block=BLOCK, mode="sort", use_mmw=False,
              m_bits=1 << 10, k_hashes=4)
    with pytest.raises(backend_lib.BackendCapabilityError):
        solver.decide(g, 3, [], schedule="while", backend="pallas", **kw)
    with pytest.raises(backend_lib.BackendCapabilityError):
        solver.decide(g, 3, [], schedule="doubling", backend="rocm", **kw)
    with pytest.raises(backend_lib.BackendCapabilityError):
        batch.decide_lanes([batch.Lane(g, 3)], cap=1 << 8, block=BLOCK,
                           mode="bloom", use_mmw=False, m_bits=100,
                           k_hashes=4, schedule="doubling",
                           backend="pallas")


def test_solve_matches_python_oracle():
    """End-to-end solve() against the exact python DP
    (``tests/oracle.py``, shared with the bounds-engine invariants)."""
    for seed in range(5):
        rng = np.random.RandomState(100 + seed)
        g = graph.gnp(8, float(rng.uniform(0.2, 0.6)), 100 + seed)
        want = oracle.tw_oracle(g)
        got = ladder.solve(g, cap=1 << 12, block=BLOCK)
        assert got.exact and got.width == want, (seed, want, got)


def _host_rungs(res, g, *, use_preprocess=True, **kw):
    """Re-decide every rung a solve ran, on the host loop: the blocks'
    ``G_k`` and cliques re-planned with the solve's defaults."""
    from repro.core import preprocess
    parts = ([b.g for b in preprocess.preprocess(g).blocks]
             if use_preprocess else [g])
    per_k = res.per_k if use_preprocess else {g.name: res.per_k}
    rungs = 0
    for part in parts:
        if part.name not in per_k:
            continue
        plan = ladder.plan_block(part, use_clique=True, use_paths=True,
                                 start_k=None)
        for k, rung in per_k[part.name].items():
            ref = solver.decide(plan.graph_at(k), k, plan.clique,
                                engine="host", **kw)
            assert (ref.feasible, ref.inexact, ref.expanded) == \
                (rung["feasible"], rung["inexact"], rung["expanded"]), \
                (part.name, k, ref, rung)
            rungs += 1
    return rungs


def test_solve_engine_agreement_end_to_end():
    """Full solve(): every rung it ran, re-decided on the host loop, gives
    the same verdict, drop flag and expanded count."""
    cases = [graph.petersen(), graph.myciel(3), graph.grid(3, 5),
             graph.gnp(13, 0.3, 7)]
    rungs = 0
    for g in cases:
        res = ladder.solve(g, cap=1 << 13, block=BLOCK)
        rungs += _host_rungs(res, g, cap=1 << 13, block=BLOCK, mode="sort",
                             use_mmw=False, m_bits=1 << 24, k_hashes=17,
                             schedule="while")
        assert res.expanded == sum(
            rung["expanded"] for block in res.per_k.values()
            for rung in block.values()), g.name
    assert rungs > 0


@pytest.mark.parametrize("cfg", CONFIGS, ids=CONFIG_IDS)
def test_lane_engine_frontier_bit_parity(cfg):
    """The multi-lane engine is a pure scheduling transform of the host
    reference loop: per-lane final frontier buffers — states, counts,
    drop accounting — are bit-identical to running each (k) alone."""
    from repro.core import batch, frontier as fr_lib

    g = graph.gnp(11, 0.35, 5)
    n, cap = g.n, 512
    adj, allowed = _devify(g)
    ks = [2, 3, 4, 5]
    b = len(ks)
    kw = dict(n=n, cap=cap, block=BLOCK, m_bits=1 << 12, k_hashes=4,
              schedule="doubling", backend="jax", **cfg)
    adj_b = jnp.broadcast_to(adj, (b,) + adj.shape)
    al_b = jnp.broadcast_to(allowed, (b,) + allowed.shape)
    fr_b = fr_lib.lane_frontiers(b, cap, adj.shape[-1])
    out_fr, _lvl, exp_b, drop_b, _ref, _app = batch._lanes_decide(
        adj_b, al_b, jnp.asarray(ks, jnp.int32),
        jnp.asarray([n - (k + 1) for k in ks], jnp.int32), fr_b, **kw)
    host_kw = {key: v for key, v in kw.items() if key != "block"}
    for i, k in enumerate(ks):
        fr_ref, exp, dropped = _host_levels(adj, allowed, k, n - (k + 1),
                                            **host_kw)
        assert exp == int(exp_b[i])
        assert (dropped > 0) == (int(drop_b[i]) > 0)
        assert (int(fr_ref.count) > 0) == (int(out_fr.count[i]) > 0)
        np.testing.assert_array_equal(np.asarray(out_fr.states[i]),
                                      np.asarray(fr_ref.states))
        np.testing.assert_array_equal(
            fr_lib.lane_to_host(out_fr, i),
            np.asarray(fr_ref.states[:int(fr_ref.count)]))


def test_solve_many_dispatch_reduction_quick_suite():
    """Acceptance criterion (ISSUE 3): solve_many over the quick suite
    matches sequential solve widths/exactness with fewer dispatches."""
    from repro.core import batch
    gs = [graph.REGISTRY[k]() for k in
          ("myciel3", "petersen", "desargues")]
    kw = dict(cap=1 << 12, block=BLOCK)
    engine.reset_counters()
    seq = [ladder.solve(g, **kw) for g in gs]
    seq_c = dict(engine.COUNTERS)
    engine.reset_counters()
    man = ladder.solve_many(gs, **kw)
    bat_c = dict(engine.COUNTERS)
    for a, b in zip(seq, man):
        assert (a.width, a.exact, a.expanded) == \
            (b.width, b.exact, b.expanded)
    assert bat_c["dispatches"] < seq_c["dispatches"]


def test_keep_levels_forces_host_engine():
    """Reconstruction replays the winning rung with keep_levels, which
    runs the host loop and returns snapshots, even from the lane ladder."""
    g = graph.petersen()
    assert solver.decide(g, 4, [], cap=1 << 13, block=BLOCK, mode="sort",
                         use_mmw=False, m_bits=1, k_hashes=1,
                         schedule="doubling", keep_levels=True,
                         engine="fused").levels
    res = ladder.solve(g, cap=1 << 13, block=BLOCK, use_preprocess=False,
                       reconstruct=True)
    assert res.order is not None
    assert solver.order_width(g, res.order) == res.width == 4


@pytest.mark.parametrize("cfg", CONFIGS, ids=CONFIG_IDS)
def test_lane_engine_backend_parity_multi_step_chunks(cfg):
    """jax vs pallas under the pool's lane vmap at the service's chunk of
    2048 states: 16 rows of 128, so every chunk runs the kernel over two
    grid steps of its default 8 rows, as on the chip.  The graph's levels
    grow past 1024 states (to 1716 in sort mode), so the second step
    holds live states too.  The filter is large enough that no two states
    of one chunk share all their probe bits: there the jax op (query the
    whole chunk, then insert) and the sequential kernel differ."""
    from repro.core import batch, frontier as fr_lib
    from repro.kernels.common import lane_geometry

    chunk = 2048
    rows, step = lane_geometry(chunk, 8)
    assert rows // step == 2
    g = graph.gnp(13, 0.2, 5)
    n, cap = g.n, chunk
    adj, allowed = _devify(g)
    ks = [4, 5]
    b = len(ks)
    out = {}
    for backend in BACKENDS:
        kw = dict(n=n, cap=cap, block=chunk, m_bits=1 << 20, k_hashes=4,
                  schedule="doubling", backend=backend, **cfg)
        out[backend] = batch._lanes_decide(
            jnp.broadcast_to(adj, (b,) + adj.shape),
            jnp.broadcast_to(allowed, (b,) + allowed.shape),
            jnp.asarray(ks, jnp.int32),
            jnp.asarray([n - (k + 1) for k in ks], jnp.int32),
            fr_lib.lane_frontiers(b, cap, adj.shape[-1]), **kw)
    (fr_j, lvl_j, exp_j, drop_j, ref_j, app_j) = out["jax"]
    (fr_p, lvl_p, exp_p, drop_p, ref_p, app_p) = out["pallas"]
    np.testing.assert_array_equal(np.asarray(lvl_j), np.asarray(lvl_p))
    np.testing.assert_array_equal(np.asarray(exp_j), np.asarray(exp_p))
    np.testing.assert_array_equal(np.asarray(drop_j), np.asarray(drop_p))
    np.testing.assert_array_equal(np.asarray(ref_j), np.asarray(ref_p))
    np.testing.assert_array_equal(np.asarray(app_j), np.asarray(app_p))
    np.testing.assert_array_equal(np.asarray(fr_j.count),
                                  np.asarray(fr_p.count))
    np.testing.assert_array_equal(np.asarray(fr_j.states),
                                  np.asarray(fr_p.states))
    assert int(np.asarray(exp_j).sum()) > 0


# ------------------------------------------------------ mid-level refill

def _golden(name):
    return oracle.golden_widths()[name]["tw"]


# (graph, k, refill cap, cap that never refills): at the refill cap a
# level's append stream passes the buffer while its distinct states fit
REFILL_LEVELS = [("petersen", 4, 128, 1 << 12),
                 ("desargues", 5, 1 << 16, 1 << 19)]


@pytest.mark.parametrize("name,k,cap,wide", REFILL_LEVELS,
                         ids=[c[0] for c in REFILL_LEVELS])
def test_refill_keeps_frontiers_level_by_level(name, k, cap, wide):
    """One level at a time: the lane program at the refill cap, the host
    loop at the same cap and the lane program at a cap that never refills
    hold the same frontier after every level, with nothing dropped."""
    from repro.core.telemetry import Tracker
    g = graph.REGISTRY[name]()
    adj, allowed = _devify(g)
    kw = dict(n=g.n, block=BLOCK, mode="sort", use_mmw=False, m_bits=1,
              k_hashes=1, schedule="doubling", backend="jax",
              use_simplicial=False)
    fr_f = frontier_lib.empty_frontier(cap, 1)
    fr_h = frontier_lib.empty_frontier(cap, 1)
    fr_w = frontier_lib.empty_frontier(wide, 1)
    tr_h = Tracker()
    refills = {"f": 0, "w": 0}
    appended = {"f": 0, "w": 0}
    widest_stream = 0
    for _level in range(g.n - (k + 1)):
        _, inexact, _, fr_f, r_f, a_f = _one_lane(
            adj, allowed, k, 1, cap=cap, fr=fr_f, **kw)
        widest_stream = max(widest_stream, a_f)
        _, inexact_w, _, fr_w, r_w, a_w = _one_lane(
            adj, allowed, k, 1, cap=wide, fr=fr_w, **kw)
        refills["f"] += r_f
        refills["w"] += r_w
        appended["f"] += a_f
        appended["w"] += a_w
        fr_h, stats = solver.run_level(adj, fr_h, k, allowed, cap=cap,
                                       tracker=tr_h, **kw)
        count = int(fr_f.count)
        assert not inexact and not inexact_w and stats.dropped == 0
        assert count == int(fr_w.count) == int(fr_h.count)
        np.testing.assert_array_equal(np.asarray(fr_f.states),
                                      np.asarray(fr_h.states))
        np.testing.assert_array_equal(np.asarray(fr_f.states)[:count],
                                      np.asarray(fr_w.states)[:count])
        if count == 0:
            break
    assert widest_stream > cap          # the append stream did not fit
    assert refills["f"] == tr_h.counters()["refills"] > 0
    assert refills["w"] == 0
    assert appended["f"] == appended["w"]


@pytest.mark.parametrize("eng", ["host", "fused"])
@pytest.mark.parametrize("name,cap", [("petersen", 64),
                                      ("desargues", 1 << 15)],
                         ids=["petersen", "desargues"])
def test_refill_solve_matches_reference(name, cap, eng):
    """At a cap the levels' append streams overflow, solve() refills and
    answers exactly, with the golden width (and the Held-Karp oracle's
    where it reaches), and no rung drops a state.  The host case
    re-decides every rung on the host loop, which refills too."""
    from repro.core.telemetry import Tracker
    g = graph.REGISTRY[name]()
    tr = Tracker()
    res = ladder.solve(g, cap=cap, block=BLOCK, tracker=tr)
    if eng == "host":
        tr = Tracker()
        assert _host_rungs(res, g, cap=cap, block=BLOCK, mode="sort",
                           use_mmw=False, m_bits=1 << 24, k_hashes=17,
                           schedule="while", tracker=tr) > 0
    assert res.exact and res.width == _golden(name)
    if g.n <= 12:
        assert res.width == oracle.tw_oracle(g)
    assert tr.counters()["refills"] > 0
    assert not any(rung["inexact"] for block in res.per_k.values()
                   for rung in block.values())


def test_refill_cannot_save_a_level_past_the_cap():
    """A level whose distinct states pass the cap still drops and counts
    it, refills or not: the decide is inexact, and so is the solve at a
    cap far below the widest level."""
    from repro.core.telemetry import Tracker
    g = graph.REGISTRY["desargues"]()
    tr = Tracker()
    [res] = batch.decide_lanes(
        [batch.Lane(g, 5)], cap=1 << 14, block=BLOCK, mode="sort",
        use_mmw=False, m_bits=1, k_hashes=1, schedule="doubling",
        tracker=tr)
    _, inexact, _, fr, refills, _ = _one_lane(
        *_devify(g), 5, g.n - 6, n=g.n, cap=1 << 14, block=BLOCK,
        mode="sort", use_mmw=False, m_bits=1, k_hashes=1,
        schedule="doubling", backend="jax", use_simplicial=False)
    assert inexact and res.inexact and int(fr.dropped) > 0
    assert refills == tr.counters()["refills"] > 0
    res = ladder.solve(g, cap=1 << 10, block=BLOCK)
    assert not res.exact and res.lb <= _golden("desargues") <= res.ub
