"""Content-addressed result cache: unit policy + differential parity
(ISSUE 10 / DESIGN.md §16).

The cache must be *invisible* in the result surface: for every golden
instance a warm resubmission returns exactly what the cold solve
returned — width, exactness, bounds, ``expanded``, ``per_k``, and
(when requested) a valid elimination order — while performing zero
device dispatches and resolving at submit time.  Failed work (cancel,
deadline, admission error) must never populate the cache, ``no_cache``
must bypass it in both directions, and the pool-scope cache counters
must reconcile exactly with the cache's own stats (§14)."""
import numpy as np
import pytest

import oracle
from repro.core import engine, graph, solver
from repro.core.telemetry import Tracker
from repro.serve import twscheduler
from repro.serve.cache import ResultCache
from repro.serve.client import TwClient
from repro.serve.twscheduler import TwScheduler
from repro.launch.twserved import TwServer

BLOCK = 32
FAST = dict(cap=1 << 12, block=BLOCK)

GOLDEN = oracle.golden_cases()
# the golden tier includes myciel4/queen5_5 — same sizing as the exact
# golden sweep in test_core_solver
GFAST = dict(cap=1 << 16, block=1 << 9)


def _res(width=3, order=None, per_k=None, expanded=10):
    return solver.SolveResult(width=width, exact=True, lb=width,
                              ub=width, expanded=expanded, time_sec=0.0,
                              order=order, per_k=per_k)


def _surface(r):
    return (r.width, r.exact, r.lb, r.ub, r.expanded, r.per_k)


# --------------------------------------------------------- LRU+pin policy

def test_lru_evicts_oldest_and_lookup_refreshes_recency():
    c = ResultCache(entries=2)
    c.insert("a", _res(1)); c.insert("b", _res(2))
    assert c.lookup("a").width == 1      # refresh a: b is now oldest
    assert c.insert("c", _res(3)) == 1
    assert "b" not in c and "a" in c and "c" in c
    assert len(c) == 2


def test_pins_survive_eviction_and_may_exceed_capacity():
    c = ResultCache(entries=1)
    c.insert("a", _res(1))
    assert c.pin("a") and not c.pin("ghost")
    assert c.insert("b", _res(2)) == 0   # a pinned, b fresh: grows past 1
    assert len(c) == 2 and c.stats()["pinned"] == 1
    assert c.unpin("a")
    assert c.insert("d", _res(4)) == 2   # eviction resumes: a and b go
    assert len(c) == 1 and "d" in c


def test_need_order_misses_orderless_then_upgrade_never_downgrades():
    c = ResultCache(entries=4)
    c.insert("k", _res(3))
    assert c.lookup("k", need_order=True) is None        # counted a miss
    assert c.lookup("k").order is None                   # plain hit fine
    c.insert("k", _res(3, order=[2, 0, 1]))              # upgrade
    assert c.lookup("k", need_order=True).order == [2, 0, 1]
    c.insert("k", _res(3))                               # would downgrade
    assert c.peek("k").order == [2, 0, 1]                # refused


def test_hits_return_private_copies():
    c = ResultCache(entries=2)
    c.insert("k", _res(3, order=[0, 1, 2], per_k={"b": {"feasible": 1}}))
    r = c.lookup("k")
    r.order.append(99); r.per_k["x"] = 1
    clean = c.peek("k")
    assert clean.order == [0, 1, 2] and "x" not in clean.per_k


def test_stats_identities_and_validation():
    with pytest.raises(ValueError):
        ResultCache(entries=0)
    c = ResultCache(entries=2)
    c.insert("a", _res(1))
    c.lookup("a"); c.lookup("a"); c.lookup("nope")
    s = c.stats()
    assert s["hits"] + s["misses"] == 3
    assert s["hits"] == 2 and s["entries"] == len(c) == 1
    assert s["insertions"] - s["evictions"] == s["entries"]
    assert s["hit_rate"] == pytest.approx(2 / 3)


# ---------------------------------------------- golden differential parity

@pytest.mark.parametrize("name,gf,want", GOLDEN,
                         ids=[n for n, _, _ in GOLDEN])
def test_golden_warm_hit_is_bit_identical_and_dispatch_free(
        name, gf, want, event_invariants):
    """Cold solve then warm resubmission per golden instance: identical
    full surface, golden width, zero device work on the hit, and a
    contract-clean event stream with the ``cached`` flag."""
    g = gf()
    sched = TwScheduler(lanes=2, cache=ResultCache(32), **GFAST)
    cold_evs, warm_evs = [], []
    r0 = sched.submit(g, on_event=cold_evs.append)
    done = sched.run()
    cold = done[r0]
    assert cold.exact and cold.width == want

    engine.reset_counters()
    r1 = sched.submit(g, on_event=warm_evs.append)
    # a hit resolves entirely at submit — before any driver round
    assert sched.terminal[r1] == "done"
    warm = sched.run()[r1]
    assert dict(engine.COUNTERS).get("dispatches", 0) == 0
    assert dict(engine.COUNTERS).get("expanded", 0) == 0
    assert _surface(warm) == _surface(cold)

    t0 = event_invariants(cold_evs, rid=r0)
    t1 = event_invariants(warm_evs, rid=r1)
    assert t0["event"] == t1["event"] == "done"
    assert not cold_evs[0].get("cached")
    assert all(e.get("cached") for e in warm_evs)
    s = sched.cache_stats()
    assert s["enabled"] and s["hits"] == 1 and s["insertions"] == 1


@pytest.mark.parametrize("backend,mode,shards",
                         [("jax", "sort", 1), ("jax", "bloom", 1),
                          ("jax", "sort", 2), ("pallas", "sort", 1)])
def test_warm_parity_across_backend_mode_shards(backend, mode, shards):
    gs = [graph.petersen(), graph.myciel(3)]
    kw = dict(cap=1 << 12, block=BLOCK, mode=mode, backend=backend,
              m_bits=1 << 14, schedule="doubling")
    sched = TwScheduler(lanes=2, cache=ResultCache(16), **kw)
    cold_r = [sched.submit(g, shards=shards) for g in gs]
    cold = sched.run()
    warm_r = [sched.submit(g, shards=shards) for g in gs]
    warm = sched.run()
    for g, rc, rw in zip(gs, cold_r, warm_r):
        assert _surface(cold[rc]) == _surface(warm[rw]), \
            (g.name, backend, mode, shards)
    assert sched.cache_stats()["hits"] == len(gs)


def test_shards_do_not_split_the_key():
    """Sharding is bit-identical to unsharded (DESIGN.md §13), so it is
    deliberately outside the key: a sharded resubmission hits the
    unsharded entry."""
    g = graph.petersen()
    sched = TwScheduler(lanes=2, cache=ResultCache(8), **FAST)
    r0 = sched.submit(g)
    cold = sched.run()[r0]
    r1 = sched.submit(g, shards=2)
    assert sched.terminal[r1] == "done"
    assert _surface(sched.run()[r1]) == _surface(cold)


def test_iso_relabeled_hit_returns_a_valid_translated_order():
    """A relabeled duplicate hits the canonical entry; the cached order
    (stored in canonical space) is translated back into *its* labels and
    must certify the same width on the relabeled graph."""
    g = graph.petersen()
    rng = np.random.RandomState(9)
    h = g.relabel(rng.permutation(g.n))
    sched = TwScheduler(lanes=2, cache=ResultCache(8), **FAST)
    r0 = sched.submit(g, reconstruct=True)
    cold = sched.run()[r0]
    assert solver.order_width(g, cold.order) == cold.width

    r1 = sched.submit(h, reconstruct=True)
    assert sched.terminal[r1] == "done"          # canonical key: a hit
    warm = sched.run()[r1]
    assert warm.width == cold.width and warm.exact
    assert sorted(warm.order) == list(range(h.n))
    assert solver.order_width(h, warm.order) == cold.width


def test_reconstruct_miss_upgrades_the_entry():
    """An order-less entry misses a reconstruct submission; the re-solve
    upgrades the entry so the *next* reconstruct submission hits."""
    g = graph.petersen()
    sched = TwScheduler(lanes=2, cache=ResultCache(8), **FAST)
    sched.submit(g); sched.run()
    r1 = sched.submit(g, reconstruct=True)
    assert sched.terminal.get(r1) != "done"      # order needed: full solve
    warm = sched.run()[r1]
    assert solver.order_width(g, warm.order) == warm.width
    r2 = sched.submit(g, reconstruct=True)
    assert sched.terminal[r2] == "done"          # upgraded entry hits now
    assert sched.run()[r2].order == warm.order


def test_bloom_hits_identical_bytes_only():
    """mode="bloom" is Monte-Carlo and label-dependent: identical
    resubmission hits, a relabeling must NOT (it would alias a different
    ``expanded`` surface)."""
    g = graph.petersen()
    rng = np.random.RandomState(3)
    h = g.relabel(rng.permutation(g.n))
    kw = dict(cap=1 << 12, block=BLOCK, mode="bloom", m_bits=1 << 14)
    sched = TwScheduler(lanes=2, cache=ResultCache(8), **kw)
    r0 = sched.submit(g)
    sched.run()
    r1 = sched.submit(g)                         # same bytes: hit
    assert sched.terminal[r1] == "done"
    r2 = sched.submit(h)                         # relabeled: fresh solve
    assert sched.terminal.get(r2) != "done"
    done = sched.run()
    assert done[r2].width == done[r0].width      # widths still agree
    assert sched.cache_stats()["insertions"] == 2


# ------------------------------------------------------- negative caching

def test_cancelled_request_is_never_inserted():
    cache = ResultCache(8)
    sched = TwScheduler(lanes=1, cache=cache, **FAST)
    rid = sched.submit(graph.queen(5))
    assert sched.cancel(rid)
    sched.run()
    assert len(cache) == 0 and cache.stats()["insertions"] == 0


def test_deadline_timeout_is_never_inserted():
    cache = ResultCache(8)
    sched = TwScheduler(lanes=1, cache=cache, **FAST)
    rid = sched.submit(graph.queen(5), deadline_s=0.0)
    res = sched.run()[rid]
    assert sched.terminal[rid] == "timeout" and not res.exact
    assert len(cache) == 0 and cache.stats()["insertions"] == 0
    # and the poisoned bounds can't be served to a later submission
    r2 = sched.submit(graph.queen(5))
    assert sched.terminal.get(r2) != "done"
    assert sched.run()[r2].exact


def test_admission_error_is_never_inserted(monkeypatch):
    cache = ResultCache(8)
    sched = TwScheduler(lanes=1, cache=cache, **FAST)

    def boom(*a, **kw):
        raise RuntimeError("admission blew up")

    monkeypatch.setattr(twscheduler.ladder, "InstanceState", boom)
    rid = sched.submit(graph.petersen())
    sched.run()
    assert sched.terminal[rid] == "error"
    assert len(cache) == 0 and cache.stats()["insertions"] == 0


def test_no_cache_bypasses_lookup_and_insert():
    cache = ResultCache(8)
    sched = TwScheduler(lanes=1, cache=cache, **FAST)
    g = graph.petersen()
    r0 = sched.submit(g, no_cache=True)          # no insert
    sched.run()
    assert len(cache) == 0
    r1 = sched.submit(g)
    cold = sched.run()[r1]
    r2 = sched.submit(g, no_cache=True)          # no lookup: fresh solve
    assert sched.terminal.get(r2) != "done"
    res = sched.run()[r2]
    s = cache.stats()
    assert s["hits"] == 0 and s["insertions"] == 1
    assert _surface(res) == _surface(cold) == _surface(sched.done[r0])


def test_heuristic_only_requests_skip_the_cache():
    cache = ResultCache(8)
    sched = TwScheduler(lanes=1, cache=cache, **FAST)
    rid = sched.submit(graph.petersen(), heuristic_only=True, seed=1)
    sched.run()
    assert rid in sched.done
    assert len(cache) == 0 and cache.stats()["misses"] == 0


# --------------------------------------------------- telemetry + the wire

def test_cache_counters_reconcile_with_cache_stats():
    """§14: pool-scope cache_{hits,misses,insertions,evictions} equal
    the cache's own stats after a mixed hit/miss stream."""
    cache = ResultCache(2)
    sched = TwScheduler(lanes=2, cache=cache, tracker=Tracker(), **FAST)
    gs = [graph.petersen(), graph.myciel(3), graph.grid(3, 4),
          graph.petersen()]
    for g in gs:
        sched.submit(g)
    sched.run()
    for g in gs[:2]:
        sched.submit(g)
    sched.run()
    pool = sched.metrics()["pool"]["counters"]
    s = cache.stats()
    for k in ("hits", "misses", "insertions", "evictions"):
        assert pool.get(f"cache_{k}", 0) == s[k], (k, pool, s)
    assert s["evictions"] > 0                    # capacity 2 really churned


def test_cache_over_the_wire():
    srv = TwServer(port=0, lanes=2, cap=1 << 12, block=BLOCK,
                   m_bits=1 << 14, cache=8)
    srv.start()
    try:
        c = TwClient(port=srv.port)
        rid = c.submit("petersen")
        cold = c.result(rid)
        s0 = c.cache_stats()
        assert s0["enabled"] and s0["insertions"] == 1

        rid2 = c.submit("petersen")
        evs = list(c.stream(rid2))
        assert evs and all(e.get("cached") for e in evs)
        warm = c.result(rid2)
        for f in ("width", "exact", "lb", "ub", "expanded", "per_k"):
            assert warm[f] == cold[f], f
        assert c.cache_stats()["hits"] == 1

        rid3 = c.submit("petersen", no_cache=True)
        bypass = c.result(rid3)
        assert bypass["width"] == cold["width"]
        s = c.cache_stats()
        assert s["hits"] == 1 and s["insertions"] == 1   # untouched
    finally:
        srv.close()


def test_cacheless_server_reports_disabled():
    sched = TwScheduler(lanes=1, **FAST)         # library default: off
    assert sched.cache_stats() == {"enabled": False}
    rid = sched.submit(graph.petersen())
    assert sched.terminal.get(rid) != "done"
    assert sched.run()[rid].exact
