"""Anytime heuristic bounds engine (DESIGN.md §15), oracle-verified.

Every claim the bounds engine makes is a certificate the suite can
check: an upper bound carries an elimination order whose host replay
(``solver.order_width``) must reproduce it, a lower bound must sit at or
below the exact treewidth (``tests/oracle.py``'s Held-Karp DP / the
golden-widths file).  The property tests pin the sandwich
``lb <= tw <= ub`` and replay-validity across random graphs and seeds;
the scheduler tests pin the monotone-tightening contract — heuristics
may shrink the exact ladder (skipped rungs), never change a verdict.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import oracle
from repro.core import bounds, bounds_engine, graph, ladder, solver, telemetry
from repro.serve.twscheduler import TwScheduler

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)


# ----------------------------------------------------- oracle sandwich (host)

@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_property_bounds_sandwich_the_exact_treewidth(seed):
    """lb <= tw <= ub for quick_bounds and any number of improver
    rounds, against the exact python DP."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(4, 10))
    g = graph.gnp(n, float(rng.uniform(0.2, 0.7)), seed)
    tw = oracle.tw_oracle(g)
    lb, ub, order = bounds_engine.quick_bounds(g, seed=seed)
    assert lb <= tw <= ub
    assert solver.order_width(g, order) == ub
    imp = bounds_engine.improve(g, lb, ub, order, rounds=3, seed=seed)
    assert lb <= imp.lb <= tw <= imp.ub <= ub


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_property_heuristic_orders_replay_to_a_width_geq_tw(seed):
    """Every heuristic elimination order is a genuine certificate: the
    host replay of the order gives exactly the reported ub, which can
    never undercut the true treewidth."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(4, 10))
    g = graph.gnp(n, float(rng.uniform(0.2, 0.7)), seed)
    tw = oracle.tw_oracle(g)
    for strat in bounds_engine._UB_STRATEGIES:
        w, order = bounds.randomized_order(g, seed, strat)
        assert oracle.order_is_valid(g, order)
        assert solver.order_width(g, order) == w >= tw


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_property_contraction_lb_below_tw(seed):
    """Every contracted graph is a minor, so the sweep's bound is
    sound."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(3, 10))
    g = graph.gnp(n, float(rng.uniform(0.15, 0.7)), seed)
    assert bounds_engine.contraction_lb(g, seed) <= oracle.tw_oracle(g)


def test_improvers_are_deterministic_per_seed():
    g = graph.mcgee()
    a = bounds_engine.improve(g, rounds=4, seed=11)
    b = bounds_engine.improve(g, rounds=4, seed=11)
    assert (a.lb, a.ub, a.ub_order) == (b.lb, b.ub, b.ub_order)
    assert bounds.randomized_order(g, 5) == bounds.randomized_order(g, 5)
    assert bounds_engine.contraction_lb(g, 7) == \
        bounds_engine.contraction_lb(g, 7)
    assert bounds.upper_bound(g, seed=3, restarts=2) == \
        bounds.upper_bound(g, seed=3, restarts=2)
    assert bounds.lower_bound(g, seed=3) == bounds.lower_bound(g, seed=3)


def test_default_seed_reproduces_the_historical_deterministic_bounds():
    """seed=0, restarts=0 must be the exact pre-seeding behaviour: the
    rank tiebreak degenerates to the vertex index."""
    for g in [graph.petersen(), graph.myciel(3), graph.grid(4, 5)]:
        ub, order = bounds.upper_bound(g)
        w, o = bounds._elimination_ub(g, "min_degree")
        w2, o2 = bounds._elimination_ub(g, "min_fill")
        assert ub == min(w, w2)
        assert order in (o, o2)


# ------------------------------------------------ batched jax kernel parity

def test_vmapped_ub_kernel_widths_match_host_replay():
    """The one-dispatch pooled sweep returns (width, order) pairs whose
    host replay reproduces the width exactly — mixed sizes padded to a
    shared n, pad vertices filtered back out."""
    gs = [graph.petersen(), graph.myciel(3), graph.grid(4, 5)]
    tr = telemetry.Tracker()
    h = bounds_engine.ub_orders_async(gs, [3, 4, 5], tracker=tr)
    out = h.result()
    assert len(out) == len(gs)
    for g, (w, order) in zip(gs, out):
        assert oracle.order_is_valid(g, order)
        assert solver.order_width(g, order) == w
    c = tr.snapshot()["counters"]
    assert c["heur_dispatches"] == 1 and c["heur_lanes"] == len(gs)


def test_vmapped_ub_kernel_is_deterministic_and_seed_sensitive():
    g = graph.petersen()
    a = bounds_engine.ub_orders_async([g], [9]).result()
    b = bounds_engine.ub_orders_async([g], [9]).result()
    assert a == b
    outs = {tuple(bounds_engine.ub_orders_async([g], [s]).result()[0][1])
            for s in range(6)}
    assert len(outs) > 1          # distinct seeds explore distinct sweeps


def test_ub_orders_async_empty_pool_is_a_noop():
    assert bounds_engine.ub_orders_async([], []).result() == []


# --------------------------------------------- exact-instance bound clamping

PLAN_KW = dict(use_clique=True, use_paths=True, start_k=None)


def test_instance_improve_bounds_clamps_the_ladder_monotonically():
    g = graph.queen(5)                     # tw 18: a long ladder
    inst = ladder.InstanceState(g, use_preprocess=False,
                                plan_kw=dict(PLAN_KW))
    run = inst.run
    lb0, ub0 = inst.bounds()
    # a worse ub (no certificate needed to reject) and a worse lb: no-op
    out = inst.improve_bounds(lb=lb0 - 1, ub=ub0 + 1, ub_order=None)
    assert out == dict(lb_improved=False, ub_improved=False,
                       rungs_skipped=0, finished=False)
    assert inst.bounds() == (lb0, ub0)
    # an improved ub without its order certificate must be rejected
    out = inst.improve_bounds(ub=ub0 - 1, ub_order=None)
    assert not out["ub_improved"] and inst.bounds() == (lb0, ub0)
    # a genuine lb jump skips the refuted rungs: run.k snaps up
    k0 = run.k
    out = inst.improve_bounds(lb=k0 + 2)
    assert out["lb_improved"] and out["rungs_skipped"] == 2
    assert run.k == k0 + 2 and inst.bounds()[0] == k0 + 2


def test_instance_improve_bounds_ub_certificate_can_finish_the_run():
    g = graph.petersen()
    inst = ladder.InstanceState(g, use_preprocess=False,
                                plan_kw=dict(PLAN_KW))
    lb0, _ub0 = inst.bounds()
    # hand it a perfect certificate: an order of the exact width, with
    # lb pushed to meet it -> the run finishes without any DP rung
    r = ladder.solve(g, reconstruct=True, use_preprocess=False, **FAST)
    out = inst.improve_bounds(lb=r.width, ub=r.width, ub_order=r.order)
    assert out["finished"] and inst.result is not None
    assert inst.result.width == r.width and inst.result.exact
    assert solver.order_width(g, inst.result.order) == r.width


# ------------------------------------------------- scheduler: exact parity

@pytest.mark.parametrize("heuristics", [0, 4])
def test_pool_with_improver_lanes_keeps_exact_verdicts(heuristics):
    """The acceptance criterion: with the bounds engine on, every final
    verdict (width, exact) is bit-identical to the sequential solver —
    the improvers may only shorten the ladder."""
    gs = [graph.petersen(), graph.myciel(3), graph.queen(4),
          graph.gnp(13, 0.3, 7)]
    sched = TwScheduler(lanes=4, heuristics=heuristics, **FAST)
    rids = [sched.submit(g) for g in gs]
    done = sched.run()
    for rid, g in zip(rids, gs):
        ref = ladder.solve(g, **FAST)
        assert (done[rid].width, done[rid].exact) == \
            (ref.width, ref.exact), g.name
        if heuristics == 0:
            # engine off: not just verdicts — bit-identical accounting
            assert (done[rid].expanded, done[rid].per_k) == \
                (ref.expanded, ref.per_k), g.name


def test_improver_lanes_skip_exact_rungs_and_stream_bounds(
        event_invariants):
    """Forcing the full ladder (start_k=0) on petersen: the improver's
    randomized sweep finds the width-4 certificate before the ladder
    climbs there, so rungs are skipped, the `bounds` event fires, the
    telemetry reconciles — and the verdict is still exactly (4, True)."""
    evs = []
    sched = TwScheduler(lanes=1, pipeline=2, heuristics=8, **FAST)
    rid = sched.submit(graph.petersen(), start_k=0, on_event=evs.append)
    done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[rid].width, done[rid].exact) == (ref.width, ref.exact)

    base = TwScheduler(lanes=1, pipeline=2, **FAST)
    rid0 = base.submit(graph.petersen(), start_k=0)
    base.run()

    snap = sched.tracker.snapshot()["counters"]
    snap0 = base.tracker.snapshot()["counters"]
    assert snap["heur_ub_improvements"] >= 1
    assert snap["exact_rungs_skipped"] >= 1
    assert snap["rungs_decided"] < snap0["rungs_decided"]
    # the pool totals reconcile the per-request child scope (§14)
    req = sched.req_metrics[rid]["counters"]
    assert req["exact_rungs_skipped"] == snap["exact_rungs_skipped"]

    assert event_invariants(evs, rid=rid)["event"] == "done"
    assert any(e["event"] == "bounds" for e in evs)


def test_solver_heuristics_knob_plans_a_tighter_ladder():
    """solve(heuristics=N) applies the same improvers at plan time:
    same verdict, never more expanded states."""
    g = graph.petersen()
    a = ladder.solve(g, start_k=0, **FAST)
    b = ladder.solve(g, start_k=0, heuristics=8, **FAST)
    assert (a.width, a.exact) == (b.width, b.exact)
    assert b.expanded <= a.expanded
    c = ladder.solve(g, start_k=0, heuristics=8, **FAST)
    assert (b.width, b.expanded, b.per_k) == (c.width, c.expanded, c.per_k)


# ------------------------------------------------ scheduler: heuristic-only

@pytest.mark.parametrize("name,spec",
                         sorted(oracle.golden_widths().items()),
                         ids=sorted(oracle.golden_widths()))
def test_heuristic_only_bounds_are_oracle_valid(name, spec,
                                                event_invariants):
    """Bounds-only serving on every golden instance — including the
    ``slow``-flagged ones the fast exact tier cannot finish: the stream
    obeys the event contract and the terminal bounds sandwich the known
    exact width, with ``exact == (lb == ub)``."""
    g = oracle.make_graph(name)
    evs = []
    sched = TwScheduler(lanes=2, **FAST)
    rid = sched.submit(g, heuristic_only=True, heuristics=6, seed=1,
                       on_event=evs.append)
    done = sched.run()
    res = done[rid]
    assert res.lb <= spec["tw"] <= res.ub, (name, res)
    assert res.exact == (res.lb == res.ub)
    assert res.width == res.ub
    assert res.order is not None
    assert oracle.order_is_valid(g, res.order)
    assert solver.order_width(g, res.order) <= res.ub
    term = event_invariants(evs, rid=rid)
    assert term["event"] == "done"
    assert (term["lb"], term["ub"]) == (res.lb, res.ub)
    assert not any(e["event"] in ("rung_started", "rung_decided")
                   for e in evs)                 # no exact rung ever ran


def test_heuristic_only_is_deterministic_per_seed():
    kw = dict(heuristic_only=True, heuristics=4, seed=5)
    outs = []
    for _ in range(2):
        sched = TwScheduler(lanes=1, **FAST)
        rid = sched.submit(graph.mcgee(), **kw)
        res = sched.run()[rid]
        outs.append((res.lb, res.ub, tuple(res.order)))
    assert outs[0] == outs[1]


def test_heuristic_only_mixes_with_exact_requests_in_one_pool():
    sched = TwScheduler(lanes=2, **FAST)
    r_h = sched.submit(graph.mcgee(), heuristic_only=True, heuristics=4)
    r_e = sched.submit(graph.petersen())
    done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[r_e].width, done[r_e].exact, done[r_e].expanded) == \
        (ref.width, ref.exact, ref.expanded)
    assert done[r_h].lb <= 7 <= done[r_h].ub     # mcgee tw = 7
    assert done[r_h].expanded == 0               # no DP work at all


def test_heuristic_only_rejects_sharding():
    sched = TwScheduler(lanes=2, **FAST)
    with pytest.raises(ValueError, match="heuristic_only"):
        sched.submit(graph.petersen(), heuristic_only=True, shards=2)
