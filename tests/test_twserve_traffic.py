"""Traffic shaping for the solve service (ISSUE 6 / DESIGN.md §12).

The SLO contract on top of §11's async pipeline: requests can be
cancelled mid-ladder (their in-flight verdicts discarded uncounted),
deadline-preempted into monotone anytime bounds, prioritised without
starving the base class, and shed with a ``retry_after`` hint when the
admission queue is bounded — while pipelined dispatch (depth > 1) keeps
the device busy across host syncs.  Throughout, every *surviving*
request's result stays bit-identical to sequential ``ladder.solve``,
and the request lifecycle can no longer lose a request: admission
failures resolve with an ``error`` terminal event, event sinks are
invoked outside the scheduler lock, and duplicate rids are rejected.
"""
import threading
import time

import pytest

from repro.core import graph, ladder
from repro.serve.slots import QueueFull, SlotPool
from repro.serve.twscheduler import TwScheduler

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)


class _Poisoned:
    """A graph-shaped object that explodes inside preprocessing."""
    n = 5
    name = "poisoned"
    adj = None


# ------------------------------------------------------ SlotPool mechanics

def test_slotpool_priority_classes_pop_most_urgent_first():
    pool = SlotPool(1)
    pool.submit("lo1"); pool.submit("lo2")
    pool.submit("hi", priority=3)
    assert pool.queue == ["hi", "lo1", "lo2"]
    assert pool._pop() == "hi"
    assert pool._pop() == "lo1"
    assert pool._pop() == "lo2"


def test_slotpool_weighted_fifo_never_starves_the_base_class():
    pool = SlotPool(1, prio_weight=2)
    for i in range(5):
        pool.submit(f"h{i}", priority=1)
    pool.submit("l0"); pool.submit("l1")
    order = [pool._pop() for _ in range(7)]
    # two preferential pops, then the base class is served once
    assert order == ["h0", "h1", "l0", "h2", "h3", "l1", "h4"]


def test_slotpool_bounded_queue_rejects_over_limit_submits():
    pool = SlotPool(1, max_queue=2)
    pool.submit("a"); pool.submit("b")
    with pytest.raises(QueueFull):
        pool.submit("c")
    assert pool.qsize == 2                     # the reject did not queue
    # admitted items free queue room
    pool.admit(lambda item: item)
    pool.submit("c")                           # fits now


def test_slotpool_discard_removes_a_queued_item():
    pool = SlotPool(1)
    pool.submit("a"); pool.submit("b", priority=1)
    assert pool.discard(lambda it: it == "b") == "b"
    assert pool.discard(lambda it: it == "b") is None
    assert pool.queue == ["a"]


# ----------------------------------------------------------- cancellation

def test_cancel_queued_request_never_runs(event_invariants):
    sched = TwScheduler(lanes=1, **FAST)
    keep = sched.submit(graph.petersen())
    evs = []
    drop = sched.submit(graph.myciel(3), on_event=evs.append)
    assert sched.cancel(drop)
    assert sched.status(drop) == {"state": "cancelled"}
    done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[keep].width, done[keep].expanded) == \
        (ref.width, ref.expanded)
    assert drop not in done
    assert event_invariants(evs, rid=drop)["event"] == "cancelled"


def test_cancel_running_request_frees_the_lane_and_keeps_parity(
        event_invariants):
    """Cancelling mid-flight discards the rid's in-flight verdicts
    uncounted; the surviving request stays bit-identical to its solo
    sequential solve."""
    sched = TwScheduler(lanes=2, **FAST)
    evs = []
    slow = sched.submit(graph.queen(6), on_event=evs.append)
    fast = sched.submit(graph.petersen())
    assert sched.launch()                      # both rungs now in flight
    assert sched.cancel(slow)
    assert sched.pool.free == 1                # the lane freed immediately
    done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[fast].width, done[fast].exact, done[fast].expanded,
            done[fast].per_k) == (ref.width, ref.exact, ref.expanded,
                                  ref.per_k)
    assert slow not in done
    assert sched.terminal[slow] == "cancelled"
    # the cancelled stream keeps the full contract (monotone bounds up
    # to the terminal event included)
    assert event_invariants(evs, rid=slow)["event"] == "cancelled"


def test_cancel_is_idempotent_and_safe_on_unknown_rids():
    sched = TwScheduler(lanes=1, **FAST)
    rid = sched.submit(graph.petersen())
    assert sched.cancel(rid)
    assert not sched.cancel(rid)               # already terminal
    assert not sched.cancel(999)               # never existed
    done = sched.run()
    assert done == {}


# --------------------------------------------------------------- deadlines

def test_deadline_preempts_mid_ladder_with_monotone_anytime_bounds(
        event_invariants):
    sched = TwScheduler(lanes=1, **FAST)
    evs = []
    rid = sched.submit(graph.queen(6), on_event=evs.append)
    assert sched.launch()
    # force the deadline into the past after the first round launched:
    # the next sync's deadline sweep must preempt the lane
    for _i, (req, _inst) in sched.pool.active():
        req.deadline = time.monotonic() - 1.0
    done = sched.run()
    res = done[rid]
    ref = ladder.solve(graph.queen(6), **FAST)
    assert not res.exact
    assert res.lb <= ref.width <= res.ub       # genuine anytime bounds
    assert res.expanded < ref.expanded         # preempted: partial work
    assert sched.terminal[rid] == "timeout"
    assert sched.status(rid)["timed_out"] is True
    assert sched.pool.free == 1                # the lane was released
    last = event_invariants(evs, rid=rid)
    assert last["event"] == "done" and last["timed_out"] is True
    assert (last["lb"], last["ub"]) == (res.lb, res.ub)
    bounds = [(e["lb"], e["ub"]) for e in evs if "lb" in e]
    assert all(a[0] <= b[0] and a[1] >= b[1]
               for a, b in zip(bounds, bounds[1:]))


def test_deadline_expired_while_queued_resolves_without_a_lane():
    sched = TwScheduler(lanes=1, **FAST)
    rid = sched.submit(graph.queen(5), deadline_s=0.0)
    done = sched.run()
    res = done[rid]
    assert not res.exact and res.expanded == 0
    assert res.lb == 0 and res.ub == graph.queen(5).n - 1
    assert sched.terminal[rid] == "timeout"


def test_unhit_deadline_changes_nothing():
    sched = TwScheduler(lanes=1, **FAST)
    rid = sched.submit(graph.petersen(), deadline_s=3600.0)
    done = sched.run()
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[rid].width, done[rid].exact, done[rid].expanded) == \
        (ref.width, ref.exact, ref.expanded)
    assert sched.terminal[rid] == "done"


# -------------------------------------------------------------- priorities

def test_high_priority_requests_jump_the_admission_queue():
    sched = TwScheduler(lanes=1, **FAST)
    lo = sched.submit(graph.myciel(3))
    hi = sched.submit(graph.petersen(), priority=5)
    order = []
    start = sched._start

    def spy(req):
        order.append(req.rid)
        return start(req)

    sched._start = spy
    done = sched.run()
    assert order[0] == hi and order[1] == lo
    for rid, g in ((lo, graph.myciel(3)), (hi, graph.petersen())):
        ref = ladder.solve(g, **FAST)
        assert (done[rid].width, done[rid].expanded) == \
            (ref.width, ref.expanded)


# ------------------------------------------------------------ backpressure

def test_bounded_queue_rejects_with_a_retry_after_hint():
    sched = TwScheduler(lanes=1, max_queue=1, **FAST)
    rid = sched.submit(graph.petersen())
    with pytest.raises(QueueFull) as ei:
        sched.submit(graph.myciel(3))
    assert ei.value.retry_after is not None and ei.value.retry_after > 0
    # the shed submit left no trace: no rid burned, no progress entry
    assert sched._next_rid == rid + 1
    done = sched.run()
    assert set(done) == {rid}


# ------------------------------------------------------ pipelined dispatch

def test_pipeline_depth_2_matches_depth_1_with_fewer_idle_syncs():
    """Depth 2 keeps a second round in flight across each host sync
    (fewer idle host-sync gaps — the device had queued work); results and
    expanded accounting stay bit-identical to depth 1 and to sequential
    ``ladder.solve``."""
    suite = [graph.queen(5), graph.myciel(3), graph.petersen()]
    refs = [ladder.solve(g, **FAST) for g in suite]
    stats = {}
    for depth in (1, 2):
        sched = TwScheduler(lanes=3, pipeline=depth, **FAST)
        rids = [sched.submit(g) for g in suite]
        done = sched.run()
        for rid, ref in zip(rids, refs):
            assert (done[rid].width, done[rid].exact, done[rid].expanded,
                    done[rid].per_k) == (ref.width, ref.exact,
                                         ref.expanded, ref.per_k)
        stats[depth] = (sched.idle_syncs, sched.covered_syncs)
    assert stats[1][1] == 0                  # depth 1 never has cover
    assert stats[2][1] > 0                   # depth 2 does
    assert stats[2][0] < stats[1][0]         # ... so fewer idle gaps


def test_pipeline_guard_still_rejects_over_depth_launches():
    sched = TwScheduler(lanes=1, pipeline=2, **FAST)
    sched.submit(graph.queen(5))
    assert sched.launch() and sched.launch()
    with pytest.raises(RuntimeError, match="in flight"):
        sched.launch()
    sched.recover()


def test_pipeline_recover_after_failed_sync_keeps_parity():
    sched = TwScheduler(lanes=1, pipeline=2, **FAST)
    rid = sched.submit(graph.queen(5))
    assert sched.launch() and sched.launch()   # two rounds in flight
    no, handles, t0 = sched._rounds[0]
    handle, metas = handles[0]
    handles[0] = (None, metas)                 # .result() -> AttributeError
    with pytest.raises(AttributeError):
        sched.sync()
    sched.recover()
    assert not sched.in_flight
    done = sched.run()                         # re-packs from host state
    ref = ladder.solve(graph.queen(5), **FAST)
    assert (done[rid].width, done[rid].exact, done[rid].expanded) == \
        (ref.width, ref.exact, ref.expanded)


# ----------------------------------------------------- lifecycle bugfixes

def test_poisoned_admission_is_isolated_and_emits_error(event_invariants):
    """An exception inside admission (preprocess/bounds/plan) must not
    lose the request or kill the queue: the request resolves with an
    ``error`` terminal event and everything behind it still runs."""
    sched = TwScheduler(lanes=1, **FAST)
    evs = []
    bad = sched.submit(_Poisoned(), on_event=evs.append)
    good = sched.submit(graph.petersen())
    done = sched.run()                         # must not raise or hang
    assert bad not in done
    assert sched.terminal[bad] == "error"
    assert "AttributeError" in sched.errors[bad]
    assert [e["event"] for e in evs] == ["admitted", "error"]
    assert event_invariants(evs, rid=bad)["event"] == "error"
    st = sched.status(bad)
    assert st["state"] == "error" and "AttributeError" in st["error"]
    ref = ladder.solve(graph.petersen(), **FAST)
    assert (done[good].width, done[good].expanded) == \
        (ref.width, ref.expanded)


def test_event_sinks_run_outside_the_scheduler_lock():
    """A sink must never be invoked under ``_lock`` (a slow sink would
    stall every lane's dispatch): from inside the callback, another
    thread can take the scheduler lock immediately."""
    sched = TwScheduler(lanes=1, **FAST)
    lock_free = []

    def probe(ev):
        got = []

        def try_lock():
            ok = sched._lock.acquire(timeout=5)
            if ok:
                sched._lock.release()
            got.append(ok)

        t = threading.Thread(target=try_lock)
        t.start()
        t.join()
        lock_free.append(got[0])

    rid = sched.submit(graph.petersen(), on_event=probe)
    done = sched.run()
    assert lock_free and all(lock_free)
    assert done[rid].width == ladder.solve(graph.petersen(), **FAST).width


def test_event_ordering_guarantees_survive_deferred_delivery(
        event_invariants):
    sched = TwScheduler(lanes=2, **FAST)
    evs = []
    rid = sched.submit(graph.queen(5), speculate=2, on_event=evs.append)
    sched.run()
    assert [e["seq"] for e in evs] == list(range(1, len(evs) + 1))
    assert evs[0]["event"] == "admitted"
    assert event_invariants(evs, rid=rid)["event"] == "done"
    ks = [e["k"] for e in evs if e["event"] == "rung_decided"]
    assert ks == sorted(ks) and ks


def test_duplicate_rid_is_rejected():
    sched = TwScheduler(lanes=1, **FAST)
    rid = sched.submit(graph.petersen())
    with pytest.raises(ValueError, match="already issued"):
        sched.submit(graph.myciel(3), rid=rid)
    fresh = sched.submit(graph.myciel(3), rid=rid + 7)   # gaps are fine
    assert fresh == rid + 7
    assert sched.submit(graph.myciel(3)) == fresh + 1


# ------------------------------------------------- the overload acceptance

def test_synthetic_overload_stream_degrades_gracefully():
    """The acceptance scenario: queue at its bound, mixed priorities, one
    deadline-bound and one cancelled request.  The service rejects with
    ``retry_after``, preempts and cancels correctly, and every surviving
    request's result is bit-identical to sequential ``ladder.solve``."""
    sched = TwScheduler(lanes=2, max_queue=2, prio_weight=2, **FAST)
    surv_a = sched.submit(graph.petersen())              # takes a lane
    doomed = sched.submit(graph.queen(6))                # takes a lane
    assert sched.launch()                                # both in flight
    surv_b = sched.submit(graph.myciel(3), priority=1)   # queued, urgent
    victim = sched.submit(graph.queen(5))                # queue at limit
    with pytest.raises(QueueFull) as ei:                 # backpressure
        sched.submit(graph.myciel(4))
    assert ei.value.retry_after is not None and ei.value.retry_after > 0
    assert sched.cancel(victim)                          # cancel queued
    surv_c = sched.submit(graph.queen(5))                # room again
    for _i, (req, _inst) in sched.pool.active():         # deadline-bind
        if req.rid == doomed:
            req.deadline = time.monotonic() - 1.0
    done = sched.run()

    assert sched.terminal[victim] == "cancelled" and victim not in done
    assert sched.terminal[doomed] == "timeout"
    ref_doomed = ladder.solve(graph.queen(6), **FAST)
    assert not done[doomed].exact
    assert done[doomed].lb <= ref_doomed.width <= done[doomed].ub
    for rid, g in ((surv_a, graph.petersen()), (surv_b, graph.myciel(3)),
                   (surv_c, graph.queen(5))):
        ref = ladder.solve(g, **FAST)
        assert (done[rid].width, done[rid].exact, done[rid].expanded,
                done[rid].per_k) == (ref.width, ref.exact, ref.expanded,
                                     ref.per_k)
