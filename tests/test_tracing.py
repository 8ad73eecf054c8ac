"""The solve path's own instrumentation (DESIGN.md §14): the ``tw.*`` host
spans of the scheduler, the ``tw.*`` named scopes in the device program's
op metadata, and the lane-fill counters of each multi-lane dispatch.

The spans are read back from a real profiler trace of a tiny solve: they
must sit on the driving thread, nest as the scheduler calls them, and
match the pool tracker's timings call for call.
"""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batch, bitset, frontier, graph
from repro.core.telemetry import Tracker
from repro.serve.twscheduler import TwScheduler

FAST = dict(cap=1 << 12, block=32)
DECIDE = dict(block=32, mode="sort", use_mmw=False, m_bits=1 << 12,
              k_hashes=4, schedule="while", backend="jax",
              use_simplicial=False)


def _trace_events(log_dir):
    """{line id: [(name, start_ns, end_ns)]} of the ``tw.*`` host events."""
    from jax.profiler import ProfileData
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in ln.events if ev.name.startswith("tw.")]
            if evs:
                lines[(plane.name, i)] = evs
    return lines


def _inside(ev, outers):
    _n, s, e = ev
    return any(os_ <= s and e <= oe for _o, os_, oe in outers)


@pytest.fixture(scope="module")
def traced_solve(tmp_path_factory):
    """petersen and a trivial graph through a 4-lane pool under the
    profiler: the trivial one is decided at admission, so every dispatch
    holds one live lane and three padded ones."""
    log_dir = tmp_path_factory.mktemp("trace")
    sched = TwScheduler(lanes=4, tracker=Tracker(), **FAST)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        rids = [sched.submit(graph.petersen()),
                sched.submit(graph.path(3))]
        done = sched.run()
    assert {done[r].width for r in rids} == {4, 1}
    return sched, _trace_events(str(log_dir))


def test_scheduler_spans_sit_on_one_thread(traced_solve):
    _sched, lines = traced_solve
    assert len(lines) == 1
    [evs] = lines.values()
    names = {n for n, _s, _e in evs}
    assert {"tw.step", "tw.launch", "tw.admit", "tw.pack", "tw.enqueue",
            "tw.poll", "tw.sync", "tw.wait", "tw.feed"} <= names


def test_scheduler_spans_nest(traced_solve):
    _sched, lines = traced_solve
    [evs] = lines.values()
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    # admission runs inside a launch or an overlapped poll, each inside
    # a driver step; the device wait and the feed inside a sync
    for name, outer in [("tw.launch", "tw.step"), ("tw.poll", "tw.step"),
                        ("tw.sync", "tw.step"),
                        ("tw.pack", "tw.launch"),
                        ("tw.enqueue", "tw.launch"),
                        ("tw.wait", "tw.sync"), ("tw.feed", "tw.sync")]:
        assert all(_inside(ev, by[outer]) for ev in by[name]), name
    assert all(_inside(ev, by["tw.launch"] + by["tw.poll"])
               for ev in by["tw.admit"])
    assert any(_inside(ev, by["tw.launch"]) for ev in by["tw.admit"])


def test_scheduler_spans_match_pool_timings(traced_solve):
    sched, lines = traced_solve
    [evs] = lines.values()
    calls = {}
    for name, _s, _e in evs:
        calls[name] = calls.get(name, 0) + 1
    timings = sched.tracker.snapshot()["timings"]
    assert calls == {name: timings[name]["calls"] for name in calls}
    assert timings["tw.admit"]["calls"] == 2


def test_padded_dispatches_count_their_slots(traced_solve):
    sched, _lines = traced_solve
    c = sched.tracker.counters()
    assert c["lane_slots"] == 4 * c["dispatches"]
    assert c["lanes_decided"] == c["dispatches"]      # one live lane each
    assert 0 < c["lane_expanded"] <= c["lane_row_slots"]


def test_server_idle_wait_is_a_span():
    from repro.launch.twserved import TwServer
    srv = TwServer(port=0, lanes=2, tracker=Tracker(), **FAST)
    srv.start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                "tw.idle" not in srv.sched.tracker.snapshot()["timings"]:
            time.sleep(0.05)
    finally:
        srv.close()
    assert srv.sched.tracker.snapshot()["timings"]["tw.idle"]["calls"] >= 1


def _lanes_args(lanes, n_pad, cap):
    w = bitset.n_words(n_pad)
    adj, allowed, ks, targets = batch._pack_lanes(lanes, n_pad, w)
    return (jnp.asarray(adj), jnp.asarray(allowed), jnp.asarray(ks),
            jnp.asarray(targets), frontier.lane_frontiers(len(lanes), cap, w))


def test_device_scopes_name_the_dedup_sorts_and_the_append_scatter():
    lanes = [batch.Lane(graph.petersen(), 3)] * 2
    text = batch._lanes_decide.lower(
        *_lanes_args(lanes, 32, 64), n=32, cap=64, **DECIDE).compile() \
        .as_text()

    def op_names(kind):
        return [re.search(r'op_name="([^"]*)"', ln).group(1)
                for ln in text.splitlines() if f" {kind}(" in ln]

    sorts, scatters = op_names("sort"), op_names("scatter")
    # the dedup sorts, the compaction sort that packs the append, and the
    # mid-level refill's dedup and compaction sorts
    assert any("/tw.dedup/" in o for o in sorts)
    assert sum("/tw.refill/" in o for o in sorts) == 2
    assert all("/tw.level/" in o and ("/tw.dedup/" in o or "/tw.append/" in o
                                      or "/tw.refill/" in o)
               for o in sorts)
    assert any("/tw.append/" in o for o in scatters)
    assert all("/tw.append/" in o or "/tw.dedup/" in o for o in scatters)


def test_fill_counters_of_one_padded_dispatch():
    """3 live lanes padded to 8: ``lane_slots`` counts the 8, and
    ``lane_row_slots`` the live lanes' levels times the cap, with each
    lane's levels taken from its own single-lane run."""
    cap = 1 << 12
    lanes = [batch.Lane(graph.petersen(), 3), batch.Lane(graph.petersen(), 4),
             batch.Lane(graph.myciel(3), 5)]
    tr = Tracker()
    res = batch.decide_lanes(lanes, cap=cap, lane_pad=8, n_pad=32,
                             tracker=tr, **DECIDE)
    levels = []
    for lane in lanes:
        _fr, level, expanded, _d, _r, _a = batch._lanes_decide(
            *_lanes_args([lane], 32, cap), n=32, cap=cap, **DECIDE)
        levels.append(int(level[0]))
        assert int(expanded[0]) == res[len(levels) - 1].expanded
    c = tr.counters()
    assert c["lanes_decided"] == 3 and c["lane_slots"] == 8
    assert c["lane_row_slots"] == cap * sum(levels) > 0
    assert c["lane_expanded"] == sum(r.expanded for r in res)
    assert c["lane_expanded"] <= c["lane_row_slots"]
    assert np.all(np.array(levels) > 0)
