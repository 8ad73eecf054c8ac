"""Open-loop load driver for the persistent solve service (twserved).

The serve/shard benches measure closed-loop throughput (submit
everything, drain); a *service* is judged under open-loop load — requests
arrive on a fixed schedule whether or not the pool has caught up, and the
number that matters is the submit→done latency distribution, tails
included.  This driver replays a fixed arrival trace (a deterministic
interleave of Table-1 instances at a constant inter-arrival gap — no
randomness, so runs are comparable across PRs) against an **embedded**
``TwServer`` over its real TCP wire, then reads each request's
submit→done latency from the service's own telemetry: the per-request
scope snapshots returned by the ``metrics`` wire op carry a
``request_s`` timing stamped at the terminal event, and ``admission_s``
(queue wait) splits out the shaping delay.

Printed per run: p50/p95/p99 submit→done latency, mean admission wait,
pool-level dispatch/sync totals — and every result is parity-asserted
against a sequential ``ladder.solve`` of the same instance, so the
driver doubles as an end-to-end wire correctness check.

    python -m benchmarks.serve_load               # fast trace (16 reqs)
    python -m benchmarks.serve_load --quick       # CI-sized (8 reqs)
    python -m benchmarks.serve_load --jsonl serve_load_metrics.jsonl
    python -m benchmarks.serve_load --trace wl_trace.jsonl --cache 64

``--jsonl PATH`` streams the service's raw telemetry mutation log
(``telemetry.JsonlSink`` attached to the pool scope) for offline
analysis; CI uploads it as an artifact.

``--trace PATH`` replays a generated workload trace
(``python -m repro.workload`` JSONL, DESIGN.md §16) instead of the
built-in interleave: each arrival's graph + knobs are submitted at its
recorded offset, parity is asserted per arrival against a deduped set
of reference solves (relabeled duplicates check the verdict surface —
the solve is label-invariant, the plan heuristics' tie-breaks are not),
and with ``--cache N`` the server runs its content-addressed result
cache — duplicate arrivals resolve at submit and the driver asserts
their per-request telemetry shows **zero device dispatches**.
"""
from __future__ import annotations

import time

from repro.core import ladder
from repro.core.canon import graph_key
from repro.launch.twserved import TwServer
from repro.serve.client import TwClient
from repro.workload import read_trace

from .common import Timer, emit, get_instance

# deterministic arrival traces: (instance key, arrival offset seconds)
_MIX = ["myciel3", "petersen", "desargues", "petersen"]
TRACE = [(_MIX[i % len(_MIX)], 0.10 * i) for i in range(16)]
TRACE_QUICK = [(_MIX[i % len(_MIX)], 0.05 * i) for i in range(8)]


def _pct(xs, q):
    """Nearest-rank percentile of a non-empty list."""
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(round(q / 100.0 * (len(ys) - 1))))]


def run(quick: bool = False, lanes: int = 4, block: int = 1 << 10,
        jsonl_path: str = None):
    trace = TRACE_QUICK if quick else TRACE
    keys = sorted({k for k, _t in trace})
    refs = {k: ladder.solve(get_instance(k), block=block) for k in keys}

    srv = TwServer(port=0, lanes=lanes, block=block,
                   metrics_jsonl=jsonl_path)
    srv.start()
    c = TwClient(port=srv.port)
    try:
        # open-loop replay: submit at each arrival offset regardless of
        # how far the pool has fallen behind
        rids = []
        t0 = time.monotonic()
        for key, offset in trace:
            lag = t0 + offset - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            rids.append((key, c.submit(key)))
        with Timer() as t_drain:
            results = {rid: c.result(rid) for _k, rid in rids}

        # parity: the wire + scheduler are pure transport/scheduling
        for key, rid in rids:
            ref, res = refs[key], results[rid]
            assert (ref.width, ref.exact, ref.expanded) == \
                (res["width"], res["exact"], res["expanded"]), \
                (key, rid, res, ref)

        # latency percentiles from the service's own metrics snapshots
        m = c.metrics()
        snaps = {int(r): s for r, s in m["requests"].items()}
        lat = [snaps[rid]["timings"]["request_s"]["total_s"]
               for _k, rid in rids]
        adm = [snaps[rid]["timings"]["admission_s"]["total_s"]
               for _k, rid in rids if "admission_s" in snaps[rid]["timings"]]
        pool = m["pool"]["counters"]
    finally:
        srv.close()

    p50, p95, p99 = _pct(lat, 50), _pct(lat, 95), _pct(lat, 99)
    wall = time.monotonic() - t0
    print(f"serve_load: {len(trace)} requests over {trace[-1][1]:.2f}s "
          f"arrivals, {lanes} lanes", flush=True)
    print(f"  submit->done latency  p50={p50 * 1e3:.1f}ms  "
          f"p95={p95 * 1e3:.1f}ms  p99={p99 * 1e3:.1f}ms", flush=True)
    print(f"  admission wait mean   "
          f"{(sum(adm) / max(len(adm), 1)) * 1e3:.1f}ms", flush=True)
    print(f"  pool totals           dispatches={int(pool['dispatches'])} "
          f"host_syncs={int(pool['host_syncs'])} "
          f"reqs_done={int(pool.get('reqs_done', 0))}", flush=True)
    print(f"  wall {wall:.2f}s (drain {t_drain.seconds:.2f}s); "
          f"parity=exact", flush=True)
    emit("serve_load/latency", p50,
         f"p50_s={p50:.4f};p95_s={p95:.4f};p99_s={p99:.4f};"
         f"n={len(trace)};lanes={lanes};"
         f"dispatches={int(pool['dispatches'])};parity=exact")
    if jsonl_path:
        print(f"-> wrote {jsonl_path}", flush=True)
    return dict(p50_s=p50, p95_s=p95, p99_s=p99, n=len(trace),
                lanes=lanes, wall_s=wall,
                dispatches=int(pool["dispatches"]),
                host_syncs=int(pool["host_syncs"]))


# result-relevant knob subset: what makes two arrivals need distinct
# reference solves (scheduling knobs — shards/speculate/priority — are
# bit-identical paths and share one reference)
_REF_KNOBS = ("mode", "use_mmw", "use_simplicial", "start_k",
              "heuristics", "seed")


def run_trace(arrivals, lanes: int = 4, block: int = 1 << 10,
              cache: int = 256, jsonl_path: str = None,
              closed: bool = False):
    """Replay a generated workload trace (``repro.workload`` arrivals)
    against an embedded server over the real wire.

    Open-loop, like ``run``; additionally exercises and checks the
    result cache: every arrival's verdict is parity-asserted against a
    reference ``ladder.solve`` deduped by (canonical graph, result-
    relevant knobs) — relabeled duplicates (``iso``) check
    ``width``/``exact`` (the verdict is label-invariant; the plan
    heuristics' greedy tie-breaks and therefore ``expanded`` are not) —
    and when the cache is on, every rid whose telemetry shows a cache
    hit is asserted to have performed **zero device dispatches**.

    ``closed=True`` switches to closed-loop replay — each arrival waits
    for its result before the next submits (offsets ignored).  Under a
    closed loop every duplicate arrives *after* its root finished, so
    with the cache on the hit count deterministically equals the
    duplicate count — what ``benchmarks/cache_effect.py`` and the CI
    smoke assert."""
    assert arrivals, "empty trace"
    refs = {}
    for a in arrivals:
        g = a.graph()
        key = (graph_key(g),
               tuple((k, a.knobs.get(k)) for k in _REF_KNOBS))
        if key not in refs:
            kn = {k: a.knobs[k] for k in _REF_KNOBS if k in a.knobs}
            refs[key] = ladder.solve(g, block=block, **kn)
        a._ref = refs[key]      # noqa: SLF001 — driver-local annotation

    srv = TwServer(port=0, lanes=lanes, block=block, cache=cache,
                   metrics_jsonl=jsonl_path)
    srv.start()
    c = TwClient(port=srv.port)
    try:
        rids = []
        results = {}
        t0 = time.monotonic()
        for a in arrivals:
            if not closed:
                lag = t0 + a.t - time.monotonic()
                if lag > 0:
                    time.sleep(lag)
            rid = c.submit(a.graph(), **a.knobs)
            rids.append((a, rid))
            if closed:
                results[rid] = c.result(rid)
        with Timer() as t_drain:
            for _a, rid in rids:
                if rid not in results:
                    results[rid] = c.result(rid)

        for a, rid in rids:
            ref, res = a._ref, results[rid]
            assert (ref.width, ref.exact) == (res["width"], res["exact"]), \
                (a.idx, a.name, rid, res, ref)
            if not a.iso:
                assert ref.expanded == res["expanded"], \
                    (a.idx, a.name, rid, res, ref)

        m = c.metrics()
        snaps = {int(r): s for r, s in m["requests"].items()}
        lat = [snaps[rid]["timings"]["request_s"]["total_s"]
               for _a, rid in rids]
        hit_lat, miss_lat, hits = [], [], 0
        hit_idxs = []
        for a, rid in rids:
            cnt = snaps[rid]["counters"]
            if cnt.get("cache_hits"):
                hits += 1
                hit_idxs.append(a.idx)
                hit_lat.append(snaps[rid]["timings"]["request_s"]
                               ["total_s"])
                # the headline guarantee: a warm hit never touches the
                # device — its request scope saw no dispatch and
                # expanded no state
                assert not cnt.get("dispatches") and \
                    not cnt.get("expanded"), (rid, cnt)
            else:
                miss_lat.append(snaps[rid]["timings"]["request_s"]
                                ["total_s"])
        pool = m["pool"]["counters"]
        cstats = c.cache_stats()
    finally:
        srv.close()

    p50, p95, p99 = _pct(lat, 50), _pct(lat, 95), _pct(lat, 99)
    wall = time.monotonic() - t0
    dups = sum(1 for a in arrivals if a.dup_of is not None)
    print(f"serve_load[trace]: {len(arrivals)} arrivals "
          f"({dups} duplicates) over {arrivals[-1].t:.2f}s, "
          f"{lanes} lanes, cache={cache}", flush=True)
    print(f"  submit->done latency  p50={p50 * 1e3:.1f}ms  "
          f"p95={p95 * 1e3:.1f}ms  p99={p99 * 1e3:.1f}ms", flush=True)
    if hit_lat:
        print(f"  warm hits {hits}: p50={_pct(hit_lat, 50) * 1e3:.2f}ms "
              f"(cold p50={_pct(miss_lat, 50) * 1e3:.1f}ms); "
              f"zero-dispatch asserted", flush=True)
    print(f"  pool totals           dispatches={int(pool['dispatches'])} "
          f"reqs_done={int(pool.get('reqs_done', 0))} "
          f"cache_hits={int(pool.get('cache_hits', 0))}", flush=True)
    print(f"  wall {wall:.2f}s (drain {t_drain.seconds:.2f}s); "
          f"parity=exact", flush=True)
    emit("serve_load/trace", p50,
         f"p50_s={p50:.4f};p95_s={p95:.4f};p99_s={p99:.4f};"
         f"n={len(arrivals)};dups={dups};hits={hits};cache={cache};"
         f"dispatches={int(pool['dispatches'])};parity=exact")
    return dict(p50_s=p50, p95_s=p95, p99_s=p99, n=len(arrivals),
                dups=dups, hits=hits, cache_entries=cache,
                lanes=lanes, wall_s=wall, closed=closed,
                hit_p50_s=_pct(hit_lat, 50) if hit_lat else None,
                miss_p50_s=_pct(miss_lat, 50) if miss_lat else None,
                dispatches=int(pool["dispatches"]),
                cache_stats=cstats, hit_idxs=hit_idxs,
                results={a.idx: results[rid] for a, rid in rids})


if __name__ == "__main__":
    import sys
    jsonl_path = None
    if "--jsonl" in sys.argv:
        jsonl_path = sys.argv[sys.argv.index("--jsonl") + 1]
    lanes = 4
    if "--lanes" in sys.argv:
        lanes = int(sys.argv[sys.argv.index("--lanes") + 1])
    if "--trace" in sys.argv:
        trace_path = sys.argv[sys.argv.index("--trace") + 1]
        cache = 256
        if "--cache" in sys.argv:
            cache = int(sys.argv[sys.argv.index("--cache") + 1])
        run_trace(read_trace(trace_path), lanes=lanes, cache=cache,
                  jsonl_path=jsonl_path, closed="--closed" in sys.argv)
    else:
        run(quick="--quick" in sys.argv, lanes=lanes,
            jsonl_path=jsonl_path)
