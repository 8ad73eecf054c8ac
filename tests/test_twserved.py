"""Persistent ``twserved`` front end: start / submit / stream / shutdown.

Runs the real TCP server in-process on an ephemeral port (one driver
thread owning JAX, stdlib socketserver threads per connection) and
drives it through ``repro.serve.client.TwClient`` — plus one raw-socket
test speaking the JSON-lines protocol by hand (the ``nc`` path from the
README cookbook).
"""
import json
import socket

import pytest

from repro.core import graph, ladder, solver
from repro.launch.twserved import TwServer
from repro.serve.client import TwClient, TwServerError

BLOCK = 32
POOL = dict(lanes=2, cap=1 << 12, block=BLOCK, m_bits=1 << 14)


@pytest.fixture()
def server():
    srv = TwServer(port=0, **POOL)       # port 0: ephemeral
    srv.start()
    yield srv
    srv.close()


def test_submit_stream_result_roundtrip(server):
    c = TwClient(port=server.port)
    assert c.ping()
    rid = c.submit("petersen")
    evs = list(c.stream(rid))
    assert evs[0]["event"] == "admitted"
    assert evs[-1]["event"] == "done"
    ks = [e["k"] for e in evs if e["event"] == "rung_decided"]
    assert ks == sorted(ks) and ks
    bounds = [(e["lb"], e["ub"]) for e in evs if "lb" in e]
    assert all(a[0] <= b[0] and a[1] >= b[1]
               for a, b in zip(bounds, bounds[1:]))

    res = c.result(rid)
    ref = ladder.solve(graph.petersen(), cap=1 << 12, block=BLOCK)
    assert (res["width"], res["exact"], res["expanded"]) == \
        (ref.width, ref.exact, ref.expanded)
    st = c.status(rid)
    assert st["state"] == "done" and st["width"] == ref.width
    # a finished request's stream replays its full history
    assert [e["seq"] for e in c.stream(rid)] == [e["seq"] for e in evs]


def test_submit_wire_graph_with_per_request_knobs(server):
    c = TwClient(port=server.port)
    g = graph.myciel(3)
    rid = c.submit(g, mode="bloom", speculate=2)     # Graph over the wire
    res = c.result(rid)
    ref = ladder.solve(g, cap=1 << 12, block=BLOCK, mode="bloom",
                       m_bits=1 << 14)
    assert (res["width"], res["exact"]) == (ref.width, ref.exact)
    rid2 = c.submit(g, reconstruct=True)
    res2 = c.result(rid2)
    assert res2["order"] is not None
    assert solver.order_width(g, res2["order"]) == res2["width"]


def test_invalid_submits_fail_per_request_and_pool_survives(server):
    c = TwClient(port=server.port)
    with pytest.raises(TwServerError, match="unknown graph"):
        c.submit("nope")
    with pytest.raises(TwServerError):
        c.submit("petersen", mode="nope")            # BackendCapabilityError
    with pytest.raises(TwServerError, match="unknown rid"):
        c.result(999)
    rid = c.submit("petersen")                       # pool still serving
    ref = ladder.solve(graph.petersen(), cap=1 << 12, block=BLOCK)
    assert c.result(rid)["width"] == ref.width


def test_raw_json_lines_socket(server):
    """The nc-equivalent: one JSON line in, JSON lines out."""
    with socket.create_connection(("127.0.0.1", server.port)) as s:
        s.sendall(b'{"op": "submit", "n": 4, "edges": '
                  b'[[0,1],[1,2],[2,3],[3,0]], "name": "c4"}\n')
        resp = json.loads(s.makefile("r").readline())
    assert resp["ok"]
    rid = resp["rid"]
    with socket.create_connection(("127.0.0.1", server.port)) as s:
        s.sendall(json.dumps({"op": "result", "rid": rid}).encode() + b"\n")
        res = json.loads(s.makefile("r").readline())
    assert res["ok"] and res["result"]["width"] == 2   # tw(C4) = 2


def test_result_eviction_bounds_server_memory():
    """keep_results caps what a long-lived server retains: the oldest
    finished requests are evicted and answer as unknown."""
    import time
    srv = TwServer(port=0, keep_results=2, **POOL)
    srv.start()
    try:
        c = TwClient(port=srv.port)
        rids = []
        for _ in range(4):
            rid = c.submit("myciel3")
            c.result(rid)                   # finish before the next one
            rids.append(rid)
        deadline = time.time() + 10         # driver evicts on its next tick
        while time.time() < deadline and len(srv.sched.done) > 2:
            time.sleep(0.1)
        assert sorted(srv.sched.done) == rids[-2:]
        assert c.status(rids[0])["state"] == "unknown"
        with pytest.raises(TwServerError, match="unknown rid"):
            c.result(rids[0])
        st = c.status(rids[-1])
        assert st["state"] == "done"
    finally:
        srv.close()


def test_shutdown_drains_and_exits():
    srv = TwServer(port=0, **POOL)
    srv.start()
    c = TwClient(port=srv.port)
    rid = c.submit("petersen")
    c.shutdown()
    srv._driver.join(timeout=120)
    assert not srv._driver.is_alive()
    assert rid in srv.sched.done        # admitted work drained before exit
    srv.close()


# --------------------------------------------- traffic shaping over the wire

def test_cancel_over_the_wire(server):
    c = TwClient(port=server.port)
    rid = c.submit("queen6_6")
    assert c.cancel(rid) is True
    assert c.cancel(rid) is False                  # idempotent
    evs = list(c.stream(rid))
    assert evs[-1]["event"] == "cancelled"
    with pytest.raises(TwServerError, match="cancelled"):
        c.result(rid)
    assert c.status(rid)["state"] == "cancelled"
    other = c.submit("petersen")                   # pool keeps serving
    ref = ladder.solve(graph.petersen(), cap=1 << 12, block=BLOCK)
    assert c.result(other)["width"] == ref.width


def test_deadline_and_priority_knobs_ride_the_submit_line(server):
    c = TwClient(port=server.port)
    # an unhit deadline and a priority class change nothing about the result
    rid = c.submit("petersen", priority=1, deadline_s=3600.0)
    res = c.result(rid)
    ref = ladder.solve(graph.petersen(), cap=1 << 12, block=BLOCK)
    assert (res["width"], res["exact"], res["expanded"]) == \
        (ref.width, ref.exact, ref.expanded)
    assert "timed_out" not in res
    # an already-expired deadline resolves with anytime bounds, flagged
    rid2 = c.submit("queen5_5", deadline_s=0.0)
    res2 = c.result(rid2)
    assert res2["timed_out"] is True and res2["exact"] is False
    assert res2["lb"] <= res2["ub"] == res2["width"]
    evs = list(c.stream(rid2))
    assert evs[-1]["event"] == "done" and evs[-1]["timed_out"] is True


def test_backpressure_rejects_with_retry_after():
    """With the driver not yet running, submits pile into the admission
    queue; past --max-queue the server sheds them with a retry_after
    hint instead of queuing unboundedly."""
    import threading

    srv = TwServer(port=0, max_queue=1, **POOL)
    acceptor = threading.Thread(target=srv._tcp.serve_forever, daemon=True)
    acceptor.start()                 # acceptor only: nothing drains the queue
    try:
        c = TwClient(port=srv.port)
        c.submit("petersen")         # fills the bounded queue
        with pytest.raises(TwServerError, match="queue full") as ei:
            c.submit("myciel3")
        assert ei.value.retry_after is not None and ei.value.retry_after > 0
        # raw wire shape: ok false + error + retry_after
        with socket.create_connection(("127.0.0.1", srv.port)) as s:
            s.sendall(b'{"op": "submit", "graph": "myciel3"}\n')
            resp = json.loads(s.makefile("r").readline())
        assert resp["ok"] is False and resp["retry_after"] > 0
    finally:
        srv._tcp.shutdown()
        srv._tcp.server_close()


def test_server_never_passes_rids_so_they_never_collide(server):
    c = TwClient(port=server.port)
    rids = [c.submit("myciel3") for _ in range(3)]
    assert rids == sorted(set(rids))               # fresh, strictly increasing


def test_eviction_skips_logs_with_blocked_readers():
    """A ``result`` reader blocked on a still-running rid must receive the
    finished result even when eviction pressure passes keep_results while
    it waits (the log is registered busy, so _evict skips it)."""
    import threading

    srv = TwServer(port=0, keep_results=1, **POOL)
    srv.start()
    try:
        c = TwClient(port=srv.port)
        slow = c.submit("queen6_6")
        got = {}

        def read_result():
            got["res"] = c.result(slow)

        t = threading.Thread(target=read_result)
        t.start()                    # blocks in iter_events on the slow rid
        for _ in range(3):           # eviction pressure while it waits
            c.result(c.submit("myciel3"))
        t.join(timeout=120)
        assert not t.is_alive()
        ref = ladder.solve(graph.queen(6), cap=1 << 12, block=BLOCK)
        assert (got["res"]["width"], got["res"]["exact"]) == \
            (ref.width, ref.exact)
    finally:
        srv.close()


def test_evict_unit_semantics_unclosed_and_busy_logs_survive():
    """White-box pin of the eviction rules: only terminal rids whose logs
    are closed and reader-free are dropped."""
    from repro.launch.twserved import _EventLog

    srv = TwServer(port=0, keep_results=1, **POOL)   # driver not started
    try:
        sched = srv.sched
        for rid, state in ((0, "done"), (1, "done"), (2, "done")):
            sched.terminal[rid] = state
            sched.done[rid] = object()
            log = _EventLog()
            log.push({"event": "done"})              # closed
            srv._logs[rid] = log
        srv._logs[1].acquire()                       # a blocked reader
        srv._logs[2].closed = False                  # terminal not delivered
        srv._evict()
        assert 0 not in sched.done                   # evictable: dropped
        assert 1 in sched.done and 2 in sched.done   # busy/unclosed: kept
    finally:
        srv._tcp.server_close()


def test_wire_responses_coerce_numpy_payloads():
    """A result carrying numpy/jax scalars or arrays (order, per_k) must
    serialize instead of dying in json.dumps."""
    import dataclasses

    import numpy as np

    srv = TwServer(port=0, **POOL)
    srv.start()
    try:
        c = TwClient(port=srv.port)
        rid = c.submit("petersen")
        res = c.result(rid)                          # finished and logged
        poisoned = dataclasses.replace(
            srv.sched.done[rid], width=np.int64(res["width"]),
            order=np.array([3, 1, 2]),
            per_k={"g": {"expanded": np.int32(7)}})
        srv.sched.done[rid] = poisoned
        res2 = c.result(rid)
        assert res2["width"] == res["width"]
        assert res2["order"] == [3, 1, 2]
        assert res2["per_k"]["g"]["expanded"] == 7
    finally:
        srv.close()


def test_driver_step_fault_is_counted_in_metrics(server, monkeypatch):
    """A scheduler step that raises keeps the pool alive (recover and
    resume) but is counted, so a fault on the chip is visible in
    ``metrics`` instead of only as a traceback on stderr."""
    real_step = server.sched.step
    faults = []

    def failing_step():
        if not faults:
            faults.append(1)
            raise RuntimeError("injected step fault")
        return real_step()

    monkeypatch.setattr(server.sched, "step", failing_step)
    c = TwClient(port=server.port)
    rid = c.submit("petersen")
    assert c.result(rid)["width"] == 4       # the pool kept serving
    assert faults
    assert c.metrics()["pool"]["counters"]["driver_errors"] == 1
