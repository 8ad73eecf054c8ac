"""Sharded requests through the solve service (ISSUE 7 / DESIGN.md §13).

A request submitted with ``shards=S`` occupies S pool slots and has its
rungs decided by S-way sharded dispatches (``core.shard``), composing
with every traffic-shaping feature from DESIGN.md §12: S-slot admission
is head-of-line (a wide request is never starved by narrow ones),
cancel/deadline release the whole slot group, priorities still reorder
the queue, bounded queues still shed.  Throughout, every request's
result stays bit-identical to sequential ``ladder.solve``.
"""
import time

import pytest

from repro.core import graph, ladder
from repro.serve.slots import QueueFull, SlotPool
from repro.serve.twscheduler import TwScheduler

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)


# ------------------------------------------------ SlotPool multi-slot width

def test_slotpool_multislot_admission_occupies_a_group():
    pool = SlotPool(4, slots_of=lambda it: it[1])
    pool.submit(("wide", 3)); pool.submit(("a", 1)); pool.submit(("b", 1))
    adm = pool.admit(lambda it: it)
    # wide takes primary slot 0 + shadows 1,2; "a" lands in 3; "b" waits
    assert [i for i, _ in adm] == [0, 3]
    assert pool.free == 0
    assert [i for i, _ in pool.active()] == [0, 3]   # shadows not listed
    pool.release(0)                 # one release recycles the whole group
    assert pool.free == 3
    adm = pool.admit(lambda it: it)
    assert adm == [(0, ("b", 1))]


def test_slotpool_head_of_line_admission_never_starves_a_wide_item():
    pool = SlotPool(2, slots_of=lambda it: it[1])
    pool.submit(("n1", 1))
    assert pool.admit(lambda it: it) == [(0, ("n1", 1))]
    pool.submit(("wide", 2)); pool.submit(("n2", 1))
    # wide is head-of-line and does not fit: n2 must NOT overtake it,
    # else a stream of narrow submits starves the wide request forever
    assert pool.admit(lambda it: it) == []
    pool.release(0)
    assert pool.admit(lambda it: it) == [(0, ("wide", 2))]
    assert pool.free == 0 and pool.qsize == 1


# ------------------------------------------------------ scheduler admission

def test_sharded_submit_validates_against_pool_size():
    sched = TwScheduler(lanes=2, **FAST)
    with pytest.raises(ValueError):
        sched.submit(graph.petersen(), shards=3)
    with pytest.raises(ValueError):
        sched.submit(graph.petersen(), shards=0)


def test_sharded_request_occupies_shards_slots():
    sched = TwScheduler(lanes=4, **FAST)
    sched.submit(graph.queen(5), shards=3)
    nar = sched.submit(graph.petersen())
    assert sched.launch()
    assert sched.pool.free == 0          # 3 + 1 slots in flight
    assert len(sched.pool.active()) == 2
    done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[nar].width, done[nar].expanded) == (ref.width, ref.expanded)


def test_mixed_stream_parity_with_sharded_and_narrow_requests(
        event_invariants):
    gs = [(graph.petersen(), 4), (graph.myciel(3), 1), (graph.queen(4), 2)]
    sched = TwScheduler(lanes=4, **FAST)
    evs = []
    rids = [sched.submit(g, shards=s, on_event=evs.append) for g, s in gs]
    done = sched.run()
    for rid, (g, s) in zip(rids, gs):
        ref = ladder.solve(g, **FAST)
        res = done[rid]
        assert (res.width, res.exact, res.expanded, res.per_k) == \
            (ref.width, ref.exact, ref.expanded, ref.per_k), (g.name, s)
    # every request saw a full monotone event stream ending in done
    # (the shared conftest contract, per rid)
    for rid in rids:
        mine = [e for e in evs if e["rid"] == rid]
        assert event_invariants(mine, rid=rid)["event"] == "done"


# -------------------------------------------------- cancel / deadline / prio

def test_cancel_sharded_request_frees_the_whole_slot_group(
        event_invariants):
    sched = TwScheduler(lanes=4, **FAST)
    evs = []
    wide = sched.submit(graph.queen(6), shards=4, on_event=evs.append)
    assert sched.launch()
    assert sched.pool.free == 0
    assert sched.cancel(wide)
    assert sched.pool.free == 4          # primary + shadows all recycled
    done = sched.run()
    assert wide not in done
    assert sched.terminal[wide] == "cancelled"
    assert event_invariants(evs, rid=wide)["event"] == "cancelled"


def test_deadline_preempts_a_sharded_request_with_anytime_bounds(
        event_invariants):
    sched = TwScheduler(lanes=4, **FAST)
    evs = []
    rid = sched.submit(graph.queen(6), shards=4, on_event=evs.append)
    assert sched.launch()
    for _i, (req, _inst) in sched.pool.active():
        req.deadline = time.monotonic() - 1.0
    done = sched.run()
    res = done[rid]
    ref = ladder.solve(graph.queen(6), **FAST)
    assert not res.exact
    assert res.lb <= ref.width <= res.ub
    assert sched.terminal[rid] == "timeout"
    assert sched.pool.free == 4          # the whole group released
    term = event_invariants(evs, rid=rid)
    assert term["event"] == "done" and term["timed_out"] is True


def test_urgent_narrow_overtakes_a_queued_wide_request():
    sched = TwScheduler(lanes=2, **FAST)
    busy = sched.submit(graph.myciel(3))          # holds one slot first
    wide = sched.submit(graph.queen(4), shards=2)  # must wait for both
    hi = sched.submit(graph.petersen(), priority=5)
    order = []
    start = sched._start

    def spy(req):
        order.append(req.rid)
        return start(req)

    sched._start = spy
    done = sched.run()
    # priority reorders ahead of the wide item (it is not head-of-line
    # for *more urgent* classes), but the wide request still completes
    assert order == [hi, busy, wide]
    for rid, g in ((busy, graph.myciel(3)), (wide, graph.queen(4)),
                   (hi, graph.petersen())):
        ref = ladder.solve(g, **FAST)
        assert (done[rid].width, done[rid].expanded) == \
            (ref.width, ref.expanded)


def test_bounded_queue_sheds_sharded_submits_too():
    sched = TwScheduler(lanes=2, max_queue=1, **FAST)
    sched.submit(graph.petersen(), shards=2)
    with pytest.raises(QueueFull) as ei:
        sched.submit(graph.myciel(3), shards=2)
    assert ei.value.retry_after is not None


# ----------------------------------------------------- scale-out regression

def test_sharded_heavy_request_finishes_in_fewer_rounds():
    """The acceptance scenario at test scale: the same heavy request
    finishes in strictly fewer scheduler rounds with ``shards=4`` (4-way
    rung dispatches + a 4-rung ladder window from its slot entitlement)
    than with ``shards=1``, while concurrent small requests still
    complete — and both runs stay bit-identical to sequential solve."""
    heavy = graph.myciel(4)
    smalls = [graph.myciel(3), graph.petersen()]
    ref_h = ladder.solve(heavy, block=1 << 10)
    ref_s = [ladder.solve(g, block=1 << 10) for g in smalls]
    done_round = {}
    for s in (1, 4):
        sched = TwScheduler(lanes=4, block=1 << 10)
        evs = []
        rid_h = sched.submit(heavy, shards=s, on_event=evs.append)
        rids = [sched.submit(g) for g in smalls]
        done = sched.run()
        done_round[s] = next(e["rounds"] for e in evs
                             if e["event"] == "done")
        rh = done[rid_h]
        assert (rh.width, rh.exact, rh.expanded, rh.per_k) == \
            (ref_h.width, ref_h.exact, ref_h.expanded, ref_h.per_k)
        for rid, ref in zip(rids, ref_s):
            assert (done[rid].width, done[rid].expanded) == \
                (ref.width, ref.expanded)
    assert done_round[4] < done_round[1], done_round
