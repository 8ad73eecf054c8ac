"""Treewidth solve service: scheduler parity, memory planning, slot pool.

The service contract (ISSUE 4 / DESIGN.md §10): N concurrent requests
through ``TwScheduler`` produce results bit-identical to per-request
``ladder.solve`` — width, exactness, bounds, ``expanded``, ``per_k`` and
(when requested) the reconstructed elimination order — in strictly fewer
dispatches, with the pooled frontier buffers sized by
``batch.plan_capacity`` instead of the fixed worst-case cap.
"""
import warnings

import numpy as np
import pytest

from repro.core import backend as backend_lib
from repro.core import batch, bitset, engine, frontier, graph, ladder, solver
from repro.serve.slots import SlotPool
from repro.serve.twscheduler import TwScheduler

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)


def _request_stream():
    """Mixed sizes and depths so lanes genuinely interleave requests."""
    return [graph.petersen(), graph.myciel(3), graph.grid(3, 4),
            graph.gnp(12, 0.3, 7), graph.desargues(), graph.petersen()]


def _serve(gs, *, lanes=3, reconstruct=False, **kw):
    sched = TwScheduler(lanes=lanes, **kw)
    rids = [sched.submit(g, reconstruct=reconstruct) for g in gs]
    done = sched.run()
    return [done[r] for r in rids], sched


# ------------------------------------------------------------ result parity

def test_service_matches_sequential_solve_with_fewer_dispatches():
    """The acceptance criterion: full result-surface parity per request,
    and the whole stream in fewer dispatches than per-request solving."""
    gs = _request_stream()
    engine.reset_counters()
    seq = [ladder.solve(g, **FAST) for g in gs]
    seq_c = dict(engine.COUNTERS)
    engine.reset_counters()
    srv, sched = _serve(gs, **FAST)
    srv_c = dict(engine.COUNTERS)
    for g, a, b in zip(gs, seq, srv):
        assert (a.width, a.exact, a.expanded, a.lb, a.ub, a.per_k) == \
            (b.width, b.exact, b.expanded, b.lb, b.ub, b.per_k), g.name
    assert srv_c["dispatches"] < seq_c["dispatches"]
    assert srv_c["host_syncs"] < seq_c["host_syncs"]
    assert sched.rounds == srv_c["dispatches"]


@pytest.mark.parametrize("backend,mode", [("jax", "sort"), ("jax", "bloom"),
                                          ("pallas", "sort")])
def test_service_backend_mode_matrix(backend, mode):
    """Parity across backend x dedup.  All instances here stay inside one
    32-vertex word, so even bloom (hash-sensitive to the padded word
    count, DESIGN.md §8/§10) is bit-identical to the solo runs."""
    gs = [graph.petersen(), graph.myciel(3), graph.grid(3, 4)]
    kw = dict(cap=1 << 12, block=BLOCK, mode=mode, backend=backend,
              m_bits=1 << 14, schedule="doubling")
    seq = [ladder.solve(g, **kw) for g in gs]
    srv, _ = _serve(gs, lanes=2, **kw)
    for g, a, b in zip(gs, seq, srv):
        assert (a.width, a.exact, a.expanded, a.per_k) == \
            (b.width, b.exact, b.expanded, b.per_k), (g.name, backend, mode)


def test_service_reconstruction_parity():
    """reconstruct=True requests return the identical certified order the
    sequential solver produces (same host-level snapshots, same backtrack),
    with expanded parity — the certification replay is uncounted."""
    gs = [graph.petersen(), graph.queen(5)]
    seq = [ladder.solve(g, reconstruct=True, **FAST) for g in gs]
    srv, _ = _serve(gs, lanes=2, reconstruct=True, **FAST)
    for g, a, b in zip(gs, seq, srv):
        assert a.order == b.order, g.name
        assert (a.width, a.exact, a.expanded) == \
            (b.width, b.exact, b.expanded), g.name
        assert solver.order_width(g, b.order) == b.width == a.width


def test_service_reconstruction_stitches_articulated_instances():
    """Reconstruction composes with preprocessing inside the service: an
    articulated instance is solved block-by-block in lanes and the block
    orders are stitched back to one certified global order."""
    adj = np.zeros((12, 12), dtype=bool)
    for u in range(5):
        for v in range(u + 1, 5):
            adj[u, v] = adj[v, u] = True
    for u in range(4, 9):
        for v in range(u + 1, 9):
            adj[u, v] = adj[v, u] = True
    adj[8, 9] = adj[9, 8] = adj[9, 10] = adj[10, 9] = True
    g = graph.Graph(12, adj, "barbell")
    ref = ladder.solve(g, reconstruct=True, **FAST)
    srv, _ = _serve([g, graph.petersen()], lanes=2, reconstruct=True, **FAST)
    assert srv[0].order is not None
    assert sorted(srv[0].order) == list(range(g.n))
    assert srv[0].order == ref.order
    assert solver.order_width(g, srv[0].order) <= srv[0].width == ref.width


def test_more_requests_than_lanes_fifo_recycling():
    """Requests beyond the pool wait in FIFO order; finished lanes recycle
    to queued requests; everything completes with per-request parity."""
    gs = [graph.petersen(), graph.myciel(3), graph.grid(3, 4),
          graph.petersen(), graph.gnp(11, 0.35, 3), graph.myciel(3),
          graph.grid(2, 5)]
    srv, sched = _serve(gs, lanes=2, **FAST)
    assert len(srv) == len(gs)
    assert sorted(sched.done) == list(range(len(gs)))
    for g, b in zip(gs, srv):
        a = ladder.solve(g, **FAST)
        assert (a.width, a.exact, a.expanded) == \
            (b.width, b.exact, b.expanded), g.name


def test_trivial_requests_never_occupy_a_lane():
    """Trivial instances (empty graph, singleton, clique: lb >= ub decides
    at plan time) finish at admission and are recycled straight through —
    a stream of only trivial requests issues zero dispatches."""
    empty = graph.Graph(0, np.zeros((0, 0), dtype=bool), "empty")
    single = graph.Graph(1, np.zeros((1, 1), dtype=bool), "single")
    gs = [empty, single, graph.complete(5)]
    engine.reset_counters()
    srv, sched = _serve(gs, lanes=2, **FAST)
    assert dict(engine.COUNTERS)["dispatches"] == 0
    assert sched.rounds == 0
    for g, b in zip(gs, srv):
        a = ladder.solve(g, **FAST)
        assert (a.width, a.exact, a.expanded) == \
            (b.width, b.exact, b.expanded), g.name


def test_service_start_k_and_forced_inexactness():
    """Per-request start_k rides through admission planning, including the
    warn-and-return path (start_k >= ub finishes at admission)."""
    g = graph.petersen()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        seq = [ladder.solve(g, use_preprocess=False, start_k=sk, **FAST)
               for sk in (1, 4, 50)]
        sched = TwScheduler(lanes=2, use_preprocess=False, **FAST)
        rids = [sched.submit(g, start_k=sk) for sk in (1, 4, 50)]
        done = sched.run()
    for sk, rid, a in zip((1, 4, 50), rids, seq):
        b = done[rid]
        assert (a.width, a.exact, a.expanded, a.lb, a.ub) == \
            (b.width, b.exact, b.expanded, b.lb, b.ub), sk


def test_service_validates_configuration_at_construction():
    with pytest.raises(backend_lib.BackendCapabilityError):
        TwScheduler(lanes=2, backend="pallas", schedule="while")
    with pytest.raises(backend_lib.BackendCapabilityError):
        TwScheduler(lanes=2, mode="nope")
    with pytest.raises(ValueError):
        TwScheduler(lanes=0)


# --------------------------------------------------------- memory planning

def test_plan_capacity_small_blocks_beat_fixed_footprint():
    """The acceptance criterion: for small blocks the planned batched
    footprint is strictly below the fixed-cap footprint — per lane and
    for the whole pool."""
    fixed = 1 << 18
    for n in (6, 10, 12, 14):
        cap = batch.plan_capacity(n, 1, lanes=8, block=1 << 11,
                                  cap_max=fixed)
        assert cap < fixed, n
        assert frontier.frontier_bytes(cap, 1, lanes=8) < \
            frontier.frontier_bytes(fixed, 1, lanes=8), n
    # n=10: 4096 rows instead of 2^18 — a 64x per-lane cut
    assert batch.plan_capacity(10, block=1 << 11, cap_max=fixed) == 4096


def test_plan_capacity_non_pow2_cap_max_is_a_ceiling():
    """An explicit cap_max must never be exceeded: non-power-of-two values
    round DOWN (100000 -> 65536), not up past the user's stated maximum."""
    assert batch.plan_capacity(25, cap_max=100_000) == 1 << 16
    assert batch.plan_capacity(25, cap_max=1 << 16) == 1 << 16


def test_scheduler_budget_survives_word_count_growth():
    """The budget outranks the cap ratchet: when a wider instance grows the
    padded word count, a cap ratcheted under W=1 must shrink so the pool
    stays within budget_bytes (lanes * cap * W * 4)."""
    budget = 2 * 1024 * 1 * 4            # exactly 2 lanes x 1024 rows x W=1
    sched = TwScheduler(lanes=2, block=BLOCK, budget_bytes=budget)
    sched.submit(graph.petersen())       # W=1 round: cap ratchets <= 1024
    sched.run()
    assert max(sched._cap_pad.values()) * 2 * 1 * 4 <= budget
    sched.submit(graph.grid(5, 8))       # one biconnected n=40 block -> W=2
    sched.run()
    w = bitset.n_words(sched._n_pad)
    assert w == 2
    assert max(sched._cap_pad.values()) * 2 * w * 4 <= budget
    assert sched.pool_bytes() <= budget


def test_plan_capacity_bounds_and_clamps():
    # power of two, floored at 32 and at the chunk block (chunk geometry
    # must match a fixed-cap run for bloom-mode bit-parity)
    assert batch.plan_capacity(1, block=32) == 32
    assert batch.plan_capacity(4, block=1 << 11) == 2048
    # large n clamps to cap_max exactly like the fixed default did
    assert batch.plan_capacity(25) == batch.DEFAULT_CAP
    assert batch.plan_capacity(64, cap_max=1 << 12) == 1 << 12
    # a budget bounds the whole pool: lanes * cap * W * 4 <= budget
    budget = 8 * 1024 * 4
    cap = batch.plan_capacity(14, 1, lanes=8, block=32,
                              budget_bytes=budget)
    assert cap * 8 * 4 <= budget
    # the budget floor never goes below the engine's smallest chunk
    assert batch.plan_capacity(14, 1, lanes=8, block=32,
                               budget_bytes=1) == 32


def test_plan_capacity_is_drop_free_for_small_blocks():
    """The parity guarantee behind auto-sizing: a planned cap never drops
    a state, so results (incl. exactness) match the fixed cap bit for
    bit.  gnp(13, .5) floods levels hard; the planned cap must hold."""
    for seed in (0, 1, 2):
        g = graph.gnp(13, 0.5, seed)
        a = ladder.solve(g, cap=batch.DEFAULT_CAP, block=BLOCK)
        b = ladder.solve(g, cap=None, block=BLOCK)
        assert (a.width, a.exact, a.expanded, a.per_k) == \
            (b.width, b.exact, b.expanded, b.per_k), seed
        assert a.exact     # nothing dropped at the planned cap either


def test_decide_lanes_auto_cap_parity():
    """decide_lanes(cap=None) plans from its largest lane and stays
    bit-identical to explicitly fixed-cap lanes."""
    gs = [graph.petersen(), graph.myciel(3), graph.grid(3, 4)]
    lanes = [batch.Lane(g, k) for g in gs for k in (2, 4)]
    kw = dict(block=BLOCK, mode="sort", use_mmw=False, m_bits=1 << 12,
              k_hashes=4, schedule="doubling")
    auto = batch.decide_lanes(lanes, cap=None, **kw)
    fixed = batch.decide_lanes(lanes, cap=1 << 12, **kw)
    for a, b in zip(auto, fixed):
        assert (a.feasible, a.inexact, a.expanded) == \
            (b.feasible, b.inexact, b.expanded)


def test_service_pool_bytes_reports_planned_footprint():
    gs = [graph.petersen(), graph.myciel(3)]
    srv, sched = _serve(gs, lanes=4, block=BLOCK)
    fixed_pool = frontier.frontier_bytes(batch.DEFAULT_CAP,
                                         bitset.n_words(32), lanes=4)
    assert 0 < sched.pool_bytes() < fixed_pool


def test_frontier_bytes_formula():
    assert frontier.frontier_bytes(1024, 1) == 4096
    assert frontier.frontier_bytes(1024, 2, lanes=8) == 8 * 1024 * 2 * 4


# -------------------------------------------------------------- slot pool

def test_slot_pool_fifo_admission_and_recycling():
    pool = SlotPool(2)
    for x in "abcd":
        pool.submit(x)
    got = pool.admit(lambda x: x.upper())
    assert got == [(0, "A"), (1, "B")]
    assert pool.active() == [(0, "A"), (1, "B")]
    pool.release(0)
    assert pool.admit(lambda x: x.upper()) == [(0, "C")]
    assert pool.busy
    pool.release(0)
    pool.release(1)
    assert pool.admit(lambda x: x.upper()) == [(0, "D")]
    pool.release(0)
    assert not pool.busy


def test_slot_pool_instant_finish_recycles_within_admission():
    """start() returning None (finished at admission) must not burn the
    slot: the same slot immediately tries the next queued item."""
    pool = SlotPool(1)
    for x in [0, 0, 3, 5]:
        pool.submit(x)
    started = pool.admit(lambda x: x if x else None)
    assert started == [(0, 3)]          # both zeros consumed, slot kept
    assert list(pool.queue) == [5]
    with pytest.raises(ValueError):
        SlotPool(0)
