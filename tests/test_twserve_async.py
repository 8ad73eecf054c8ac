"""Async streaming solve service (ISSUE 5 / DESIGN.md §11).

The contract on top of §10's: dispatches launch without blocking
(``decide_lanes_async`` / ``DispatchHandle``), admission and planning of
newly arrived requests overlap the in-flight device work (they are
packed into the *next* dispatch, never waiting for an idle pool),
per-request knob overrides coexist in one pool via config-group
sub-dispatches, and every request can stream per-rung events whose
running lb/ub are monotone and whose ordering is pinned — all while
results stay bit-identical to sequential ``ladder.solve``.
"""
import pytest

from repro.core import backend as backend_lib
from repro.core import batch, engine, graph, ladder
from repro.serve.twscheduler import TwScheduler

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)
LANE_KW = dict(block=BLOCK, mode="sort", use_mmw=False, m_bits=1 << 12,
               k_hashes=4, schedule="while")


# ------------------------------------------------------- dispatch handles

def test_decide_lanes_async_matches_blocking_and_defers_the_sync():
    """The async launch counts its dispatch immediately but no host sync
    until ``result()``; verdicts are identical to the blocking call and
    cached on the handle."""
    lanes = [batch.Lane(graph.petersen(), k) for k in (2, 3, 4)]
    engine.reset_counters()
    h = batch.decide_lanes_async(lanes, **FAST, mode="sort", use_mmw=False,
                                 m_bits=1 << 12, k_hashes=4,
                                 schedule="while")
    c = dict(engine.COUNTERS)
    assert c["dispatches"] == 1 and c["host_syncs"] == 0
    res = h.result()
    assert engine.COUNTERS["host_syncs"] == 1
    assert h.result() is res                       # cached
    assert engine.COUNTERS["host_syncs"] == 1      # ... without a resync
    blocking = batch.decide_lanes(lanes, **FAST, mode="sort",
                                  use_mmw=False, m_bits=1 << 12,
                                  k_hashes=4, schedule="while")
    for a, b in zip(res, blocking):
        assert (a.feasible, a.inexact, a.expanded) == \
            (b.feasible, b.inexact, b.expanded)


def test_decide_lanes_async_empty_is_a_noop():
    engine.reset_counters()
    assert batch.decide_lanes_async([], **LANE_KW).result() == []
    assert all(v == 0 for v in engine.COUNTERS.values()), engine.COUNTERS


# ------------------------------------------------------------- streaming

def _collect(sched, gs, **per_req):
    events = {}
    rids = []
    for g in gs:
        evs = []
        rid = sched.submit(g, on_event=evs.append, **per_req)
        events[rid] = evs
        rids.append(rid)
    done = sched.run()
    return rids, events, done


def test_event_stream_order_and_monotone_bounds(event_invariants):
    """Per request: seq strictly increases, a block's rung_decided ks
    arrive in increasing order, lb never decreases, ub never increases,
    lb <= ub throughout, and the final done event is last and consistent
    with the result (lb meets ub at the width when exact) — the shared
    ``conftest.check_event_stream`` contract."""
    sched = TwScheduler(lanes=2, **FAST)
    rids, events, done = _collect(sched, [graph.petersen(), graph.queen(5)])
    for rid in rids:
        evs = events[rid]
        assert evs[0]["event"] == "admitted"
        d = event_invariants(evs, rid=rid)
        r = done[rid]
        assert d["event"] == "done"
        assert (d["width"], d["exact"], d["expanded"]) == \
            (r.width, r.exact, r.expanded)
        assert d["ub"] == r.width
        if r.exact:
            assert d["lb"] == r.width


def test_streamed_per_k_deltas_reassemble_the_result_per_k():
    """The rung_decided deltas are the per_k dict: reassembling them per
    block reproduces the result's per_k (and the sequential solver's)."""
    g = graph.queen(5)
    sched = TwScheduler(lanes=1, **FAST)
    (rid,), events, done = _collect(sched, [g])
    got = {}
    for e in events[rid]:
        if e["event"] == "rung_decided":
            got.setdefault(e["block"], {})[e["k"]] = {
                "feasible": e["feasible"], "inexact": e["inexact"],
                "expanded": e["expanded"]}
    res = done[rid]
    searched = {blk: pk for blk, pk in res.per_k.items() if pk}
    assert got == searched
    seq = ladder.solve(g, **FAST)
    assert res.per_k == seq.per_k


def test_broken_event_sink_does_not_break_the_solve():
    def sink(ev):
        raise RuntimeError("boom")
    sched = TwScheduler(lanes=1, **FAST)
    with pytest.warns(UserWarning, match="event sink"):
        rid = sched.submit(graph.petersen(), on_event=sink)
        done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert done[rid].width == ref.width


# -------------------------------------------------- per-request overrides

def test_mixed_per_request_configs_in_one_pool_match_solo_solves():
    """One pool, four configs: pool-default sort, a bloom request, an MMW
    request, an explicit-cap request.  Each result matches its own
    sequential solve; incompatible configs ran as sub-pool dispatches
    (more dispatches than steps)."""
    pool_kw = dict(block=BLOCK, m_bits=1 << 14, cap=1 << 12)
    reqs = [
        (graph.petersen(), {}),
        (graph.myciel(3), {"mode": "bloom"}),       # one word: bit parity
        (graph.grid(3, 4), {"use_mmw": True}),
        (graph.desargues(), {"cap": 1 << 11}),
    ]
    sched = TwScheduler(lanes=4, **pool_kw)
    engine.reset_counters()
    rids = [sched.submit(g, **kw) for g, kw in reqs]
    done = sched.run()
    c = dict(engine.COUNTERS)
    # >= 2 config groups coexisted, so some step issued several dispatches
    assert c["dispatches"] > sched.rounds
    for rid, (g, kw) in zip(rids, reqs):
        solo_kw = dict(pool_kw)
        solo_kw["cap"] = kw.get("cap", solo_kw["cap"])
        if "mode" in kw:
            solo_kw["mode"] = kw["mode"]
        a = ladder.solve(g, use_mmw=kw.get("use_mmw", False), **solo_kw)
        b = done[rid]
        assert (a.width, a.exact, a.lb, a.ub) == \
            (b.width, b.exact, b.lb, b.ub), g.name
        if not kw.get("use_mmw"):
            # bit parity; under MMW padding rows may change expanded
            # (documented §8/§10 caveat), verdicts never
            assert (a.expanded, a.per_k) == (b.expanded, b.per_k), g.name


def test_per_request_speculate_keeps_parity_in_fewer_rounds():
    g = graph.queen(5)
    seq = ladder.solve(g, **FAST)
    rungs = sum(len(pk) for pk in seq.per_k.values())
    assert rungs > 1, "need a multi-rung ladder for this test"
    one = TwScheduler(lanes=4, **FAST)
    r1 = one.submit(g)
    spec = TwScheduler(lanes=4, **FAST)
    r4 = spec.submit(g, speculate=4)
    a, b = one.run()[r1], spec.run()[r4]
    for res in (a, b):
        assert (res.width, res.exact, res.expanded, res.per_k) == \
            (seq.width, seq.exact, seq.expanded, seq.per_k)
    assert spec.rounds < one.rounds


def test_budget_splits_across_a_steps_concurrent_dispatches():
    """All of a step's dispatches are device-resident before any sync,
    so a pool budget must bound their SUM: two config groups in one
    step each get half the budget."""
    from repro.core import bitset
    budget = 2 * 1024 * 1 * 4 * 2        # two groups of lanes=2 x 1024 x W=1
    sched = TwScheduler(lanes=2, block=BLOCK, budget_bytes=budget)
    r0 = sched.submit(graph.petersen())
    r1 = sched.submit(graph.myciel(3), use_mmw=True)   # second group
    assert sched.launch()
    assert sched.inflight_dispatches == 2   # one dispatch per config group
    w = bitset.n_words(sched._n_pad)
    resident = sum(cap * 2 * w * 4 for cap in sched._cap_pad.values())
    assert resident <= budget
    sched.sync()
    done = sched.run()
    # a binding budget may reintroduce drops (documented §10): results
    # stay correct-as-upper-bounds and every request completes
    assert set(done) == {r0, r1}
    assert done[r0].width >= ladder.solve(graph.petersen(),
                                          block=BLOCK).width
    assert done[r1].width >= ladder.solve(graph.myciel(3), use_mmw=True,
                                          block=BLOCK).width


def test_recover_after_failed_step_keeps_serving():
    """recover() clears in-flight state after a raising step; the rungs
    re-pack from unchanged host state and results stay correct."""
    sched = TwScheduler(lanes=2, **FAST)
    rid = sched.submit(graph.petersen())
    assert sched.launch()
    # simulate a sync-side failure: poison the handle, then recover
    no, handles, t0 = sched._rounds[0]
    handle, metas = handles[0]
    handles[0] = (None, metas)                  # .result() -> AttributeError
    with pytest.raises(AttributeError):
        sched.sync()
    sched.recover()
    assert not sched.in_flight
    done = sched.run()                           # re-packs the same rung
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[rid].width, done[rid].exact) == (ref.width, ref.exact)


def test_per_request_capability_error_is_per_request():
    """A bad override fails its submit alone; the pool keeps serving."""
    sched = TwScheduler(lanes=2, **FAST)
    with pytest.raises(backend_lib.BackendCapabilityError):
        sched.submit(graph.petersen(), mode="nope")
    with pytest.raises(ValueError):
        sched.submit(graph.petersen(), cap=100)      # not a clean geometry
    rid = sched.submit(graph.petersen())
    ref = ladder.solve(graph.petersen(), **FAST)
    assert sched.run()[rid].width == ref.width


# ------------------------------------------------------- overlap pipeline

def test_late_arrival_is_admitted_during_an_inflight_dispatch():
    """The acceptance criterion: submit while a dispatch is in flight;
    the request takes a free slot *before* the verdict sync
    (COUNTERS-asserted: zero host syncs between launch and admission)
    and its first rung rides the very next dispatch."""
    sched = TwScheduler(lanes=2, **FAST)
    r0 = sched.submit(graph.queen(5))
    engine.reset_counters()
    assert sched.launch()
    assert sched.in_flight
    launch_c = dict(engine.COUNTERS)
    assert launch_c["host_syncs"] == 0      # verdict not read yet

    evs = []
    r1 = sched.submit(graph.petersen(), on_event=evs.append)
    sched.poll_admissions()                 # overlap bookkeeping
    # admitted into a free slot while round 1 is still un-synced
    assert engine.COUNTERS["host_syncs"] == 0
    assert sched.in_flight
    assert any(req.rid == r1 for _i, (req, _s) in sched.pool.active())
    admitted = [e for e in evs if e["event"] == "admitted"]
    assert admitted and admitted[0]["round"] == 2

    sched.sync()
    done = sched.run()
    first_rung = next(e for e in evs if e["event"] == "rung_started")
    assert first_rung["round"] == 2         # the very next dispatch
    for rid, g in ((r0, graph.queen(5)), (r1, graph.petersen())):
        a = ladder.solve(g, **FAST)
        b = done[rid]
        assert (a.width, a.exact, a.expanded, a.per_k) == \
            (b.width, b.exact, b.expanded, b.per_k), g.name


def test_overlap_beats_blocking_two_phase_round_count():
    """Step-count evidence: a late burst overlapped into a draining pool
    completes in fewer scheduler rounds than the blocking pattern (drain
    to idle, then serve the burst)."""
    early, late = [graph.queen(5)], [graph.petersen(), graph.myciel(3)]

    blocking = TwScheduler(lanes=4, **FAST)
    for g in early:
        blocking.submit(g)
    blocking.run()                           # wait for pool idle ...
    for g in late:
        blocking.submit(g)
    blocking.run()                           # ... then serve the burst

    overlap = TwScheduler(lanes=4, **FAST)
    rids = [overlap.submit(g) for g in early]
    assert overlap.launch()
    rids += [overlap.submit(g) for g in late]   # lands mid-flight
    overlap.poll_admissions()
    overlap.sync()
    done = overlap.run()

    assert overlap.rounds < blocking.rounds
    for rid, g in zip(rids, early + late):
        a = ladder.solve(g, **FAST)
        b = done[rid]
        assert (a.width, a.exact, a.expanded) == \
            (b.width, b.exact, b.expanded), g.name


def test_launch_twice_without_sync_is_an_error():
    sched = TwScheduler(lanes=1, **FAST)
    sched.submit(graph.petersen())
    assert sched.launch()
    with pytest.raises(RuntimeError, match="in flight"):
        sched.launch()
    sched.sync()
    sched.run()


def test_status_snapshots():
    sched = TwScheduler(lanes=1, **FAST)
    r0 = sched.submit(graph.queen(5))
    r1 = sched.submit(graph.petersen())
    assert sched.status(r0)["state"] == "queued"
    sched.launch()
    assert sched.status(r0)["state"] == "running"
    assert sched.status(r1)["state"] == "queued"   # pool full: 1 lane
    assert sched.status(999)["state"] == "unknown"
    sched.sync()
    sched.run()
    st = sched.status(r0)
    assert st["state"] == "done" and st["width"] == sched.done[r0].width
