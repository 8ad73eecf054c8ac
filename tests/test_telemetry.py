"""The scoped telemetry layer (DESIGN.md §14): tracker tree semantics,
sinks, thread safety, and the deprecated ``engine.COUNTERS`` view.

The contract under test: counts and timings **write through** to every
ancestor atomically (a child scope's counters sum into its parents by
construction), plain gauges stay on their own scope, ``gauge_max``
ratchets the whole ancestor chain, ``NullTracker`` is a true no-op, and
the legacy ``COUNTERS`` mapping is a frozen read-only window over the
process root — the shape ~30 pre-telemetry tests assert against.
"""
import io
import json
import threading

import pytest

from repro.core import engine, graph, ladder, telemetry
from repro.core.telemetry import (InMemorySink, JsonlSink, NullTracker,
                                  StdoutSink, Tracker)


# --------------------------------------------------------- tree semantics

def test_count_writes_through_to_every_ancestor():
    root = Tracker()
    pool = root.child("pool")
    req = pool.child("req0")
    req.count(expanded=3)
    req.count(expanded=2, rungs=1)
    for tr in (req, pool, root):
        assert tr["expanded"] == 5
        assert tr["rungs"] == 1


def test_sibling_scopes_sum_into_parent():
    root = Tracker()
    a, b = root.child("a"), root.child("b")
    a.count(x=2)
    b.count(x=5)
    assert a["x"] == 2 and b["x"] == 5
    assert root["x"] == 7


def test_gauge_stays_on_its_scope():
    root = Tracker()
    child = root.child("c")
    child.gauge("depth", 4)
    assert child["depth"] == 4
    assert root["depth"] == 0     # last-value gauges do not roll up


def test_gauge_max_ratchets_self_and_ancestors():
    root = Tracker()
    a, b = root.child("a"), root.child("b")
    a.gauge_max("peak", 10)
    b.gauge_max("peak", 7)
    a.gauge_max("peak", 3)        # lower: no change anywhere
    assert a["peak"] == 10 and b["peak"] == 7
    assert root["peak"] == 10     # parent peak = max over children


def test_timing_accumulates_and_rolls_up():
    root = Tracker()
    child = root.child("c")
    child.timing("span", 0.5)
    with child.time_block("span"):
        pass
    for tr in (child, root):
        t = tr.snapshot()["timings"]["span"]
        assert t["calls"] == 2
        assert t["total_s"] >= 0.5
        assert t["max_s"] >= 0.5


def test_span_rolls_up_like_timing():
    """``span`` is a ``time_block`` that also opens a profiler annotation:
    its timing lands under the same name and rolls up the same way."""
    root = Tracker()
    child = root.child("c")
    child.timing("tw.step", 0.5)
    with child.span("tw.step"):
        pass
    for tr in (child, root):
        t = tr.snapshot()["timings"]["tw.step"]
        assert t["calls"] == 2
        assert t["total_s"] >= 0.5
        assert t["max_s"] >= 0.5


def test_span_records_on_exception():
    tr = Tracker()
    with pytest.raises(RuntimeError):
        with tr.span("tw.sync"):
            raise RuntimeError("boom")
    assert tr.snapshot()["timings"]["tw.sync"]["calls"] == 1


def test_child_is_idempotent_per_name():
    root = Tracker()
    assert root.child("x") is root.child("x")
    assert root.child("x") is not root.child("y")


def test_drop_child_keeps_contributions_in_ancestors():
    root = Tracker()
    req = root.child("req0")
    req.count(expanded=9)
    root.drop_child("req0")
    assert root["expanded"] == 9
    assert "req0" not in root.snapshot()["children"]
    # the name can be reused by a fresh scope
    again = root.child("req0")
    assert again is not req
    assert again["expanded"] == 0


def test_snapshot_shape_and_children_toggle():
    root = Tracker()
    root.child("c").count(n=1)
    root.gauge("g", 2)
    snap = root.snapshot()
    assert snap["counters"] == {"n": 1}
    assert snap["gauges"] == {"g": 2}
    assert snap["children"]["c"]["counters"] == {"n": 1}
    assert "children" not in root.snapshot(children=False)
    # plain JSON all the way down (the wire/metrics-op requirement)
    json.dumps(snap)


def test_reset_zeroes_tree_but_keeps_structure():
    root = Tracker()
    c = root.child("c")
    c.count(n=3)
    root.gauge("g", 1)
    root.reset()
    assert root["n"] == 0 and root["g"] == 0 and c["n"] == 0
    assert root.child("c") is c


# ------------------------------------------------------------------ sinks

def test_inmemory_sink_sees_descendant_records_in_order():
    sink = InMemorySink()
    root = Tracker(sinks=[sink])
    req = root.child("pool").child("req0")
    req.count(expanded=2)
    req.gauge("depth", 1)
    req.gauge_max("peak", 5)
    req.timing("span", 0.1)
    kinds = [r["kind"] for r in sink.records]
    assert kinds == ["count", "gauge", "gauge_max", "time"]
    assert all(r["scope"] == "pool/req0" for r in sink.records)
    assert sink.records[0]["counters"] == {"expanded": 2}
    sink.clear()
    assert sink.records == []


def test_sink_attached_mid_tree_sees_only_its_subtree():
    root_sink, pool_sink = InMemorySink(), InMemorySink()
    root = Tracker(sinks=[root_sink])
    pool = root.child("pool")
    pool.add_sink(pool_sink)
    pool.child("req0").count(n=1)
    root.child("other").count(n=1)
    assert len(root_sink.records) == 2
    assert len(pool_sink.records) == 1    # only the pool subtree


def test_jsonl_sink_appends_parseable_lines():
    buf = io.StringIO()
    root = Tracker(sinks=[JsonlSink(buf)])
    root.count(a=1)
    root.count(a=2)
    lines = [json.loads(s) for s in buf.getvalue().splitlines()]
    assert [r["counters"]["a"] for r in lines] == [1, 2]
    assert all("ts" in r and "scope" in r for r in lines)


def test_jsonl_sink_file_roundtrip(tmp_path):
    path = tmp_path / "metrics.jsonl"
    sink = JsonlSink(path)
    root = Tracker(sinks=[sink])
    root.count(a=1)
    root.gauge("g", 3)
    sink.close()
    records = [json.loads(s) for s in path.read_text().splitlines()]
    assert [r["kind"] for r in records] == ["count", "gauge"]


def test_stdout_sink_formats_each_kind():
    buf = io.StringIO()
    root = Tracker(sinks=[StdoutSink(buf)])
    root.count(a=1)
    root.gauge("g", 2)
    root.timing("t", 0.25)
    out = buf.getvalue().splitlines()
    assert len(out) == 3
    assert all(line.startswith("[telemetry]") for line in out)


# ---------------------------------------------------------- thread safety

def test_concurrent_counts_from_threads_land_exactly():
    """The satellite regression for the twserved race: many threads
    hammering ``count`` on distinct child scopes (plus the root) must
    produce exact totals — no lost updates."""
    root = Tracker()
    n_threads, n_iters = 8, 500
    barrier = threading.Barrier(n_threads)

    def hammer(i):
        child = root.child(f"t{i}")
        barrier.wait()
        for _ in range(n_iters):
            child.count(hits=1)
            root.count(direct=1)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert root["hits"] == n_threads * n_iters
    assert root["direct"] == n_threads * n_iters
    for i in range(n_threads):
        assert root.child(f"t{i}")["hits"] == n_iters


def test_concurrent_gauge_max_keeps_true_peak():
    root = Tracker()
    vals = list(range(1, 201))

    def hammer(chunk):
        child = root.child(f"c{chunk[0]}")
        for v in chunk:
            child.gauge_max("peak", v)

    chunks = [vals[i::4] for i in range(4)]
    threads = [threading.Thread(target=hammer, args=(c,)) for c in chunks]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert root["peak"] == 200


# ------------------------------------------------- legacy COUNTERS window

def test_counters_view_is_read_only():
    with pytest.raises(TypeError):
        engine.COUNTERS["dispatches"] = 1


def test_counters_view_is_frozen_to_legacy_keys():
    engine.reset_counters()
    assert set(engine.COUNTERS) == set(telemetry.LEGACY_KEYS)
    assert len(engine.COUNTERS) == len(telemetry.LEGACY_KEYS)
    with pytest.raises(KeyError):
        engine.COUNTERS["lane_expanded"]
    # new counters landing in the root never widen the legacy window
    telemetry.root().count(lane_expanded=7)
    assert "lane_expanded" not in dict(engine.COUNTERS)
    engine.reset_counters()


def test_counters_view_reads_the_root_tracker():
    engine.reset_counters()
    assert all(v == 0 for v in engine.COUNTERS.values())
    telemetry.root().count(dispatches=2, host_syncs=1)
    telemetry.root().gauge_max("shard_peak_occupancy", 5)
    c = dict(engine.COUNTERS)
    assert c["dispatches"] == 2
    assert c["host_syncs"] == 1
    assert c["shard_peak_occupancy"] == 5   # gauge read-through
    engine.reset_counters()
    assert all(v == 0 for v in engine.COUNTERS.values())


def test_engine_count_shim_still_feeds_the_root():
    engine.reset_counters()
    engine.count(dispatches=1)
    engine.count(host_syncs=2)
    assert engine.COUNTERS["dispatches"] == 1
    assert engine.COUNTERS["host_syncs"] == 2
    engine.reset_counters()


# -------------------------------------------------- NullTracker + opt-out

def test_null_tracker_is_inert():
    n = telemetry.NULL
    assert isinstance(n, NullTracker)
    assert n.child("x") is n
    n.count(a=1)
    n.gauge("g", 2)
    n.gauge_max("m", 3)
    n.timing("t", 0.1)
    with n.time_block("t"):
        pass
    assert n["a"] == 0 and n.counters() == {}
    assert n.snapshot()["counters"] == {}


def test_null_tracker_span_is_the_shared_noop():
    n = telemetry.NULL
    assert n.span("tw.step") is n.time_block("tw.step")
    with n.span("tw.step"):
        pass
    assert n.snapshot()["timings"] == {}


def test_null_tracker_leaves_solo_solve_counters_unchanged():
    """The overhead opt-out: a solo fused ``solve`` routed through
    ``NULL`` must leave the process-global dispatch accounting exactly
    as it found it, while the default (root) path still counts."""
    g = graph.petersen()
    engine.reset_counters()
    res_null = ladder.solve(g, cap=1 << 12, block=32,
                            tracker=telemetry.NULL)
    assert all(v == 0 for v in engine.COUNTERS.values())

    res_root = ladder.solve(g, cap=1 << 12, block=32)
    assert engine.COUNTERS["dispatches"] > 0
    assert (res_null.width, res_null.exact, res_null.expanded) == \
        (res_root.width, res_root.exact, res_root.expanded)
    engine.reset_counters()


def test_detached_tracker_isolates_a_measurement():
    """The benchmark idiom: a fresh ``Tracker()`` given to ``solve``
    captures that run's counters without touching the root."""
    g = graph.petersen()
    engine.reset_counters()
    tr = Tracker()
    res = ladder.solve(g, cap=1 << 12, block=32, tracker=tr)
    assert res.width == 4
    assert tr["dispatches"] > 0
    assert tr["expanded"] == res.expanded
    assert all(v == 0 for v in engine.COUNTERS.values())
