"""Solve-service throughput bench: per-request solving vs the
continuous-batching lane scheduler, blocking vs async overlap
(``repro.serve.twscheduler``).

ISSUE 4's motivation quantified, extended with ISSUE 5's overlap
pipeline: a service answering one solve request at a time issues one
fused dispatch per (request, block, k) and the device idles between
them; the lane scheduler packs every in-flight request's current
deepening rung into shared multi-lane dispatches and right-sizes the
pooled frontier buffers with ``batch.plan_capacity``; the async
scheduler additionally admits requests arriving *mid-flight* into the
very next dispatch instead of waiting for an idle pool.  This bench
pushes a mixed Table-1 instance stream through

  * ``sequential`` — ``[ladder.solve(g) for g in stream]`` (per-request)
  * ``service=L``  — ``TwScheduler(lanes=L)``, blocking drain
  * ``async=L``    — the same stream with its second half arriving while
    the first dispatch is in flight, vs the blocking two-phase pattern
    (drain to idle, then serve the burst)
  * ``pipeline=D`` — ISSUE 6's pipelined dispatch: depth 2 launches the
    next round's projected rungs before the previous round syncs, so
    each host sync finds the device already covered by queued work
    (``covered_syncs`` vs ``idle_syncs``) — parity asserted against
    depth 1 and the sequential baseline
  * ``shards=S``   — ISSUE 7's intra-request scale-out: one heavy
    request submitted with ``shards=4`` (its rungs decided by 4-way
    sharded dispatches with work donation, its ladder climbing 4 rungs
    per round from its 4-slot entitlement) finishes in measurably fewer
    scheduler rounds than the same request with ``shards=1``, while the
    concurrent small requests keep completing — parity asserted for
    every request in both runs

and reports requests/sec, dispatch/host-sync/round counts and the pooled
frontier footprint, asserting full result parity (width/exactness/
expanded — the default config carries no padding caveat) including the
per_k reassembled from the streamed ``rung_decided`` events, plus the
dispatch reduction and the mid-flight-admission round evidence.  On CPU
absolute times measure XLA's CPU backend; the dispatch/round reduction
is the portable signal (wall-clock becomes meaningful on real TPU
hardware, as with engine_sync).

    python -m benchmarks.serve_throughput              # fast stream
    python -m benchmarks.serve_throughput --quick      # CI-sized
    python -m benchmarks.serve_throughput --full
    python -m benchmarks.serve_throughput --lanes 16
    python -m benchmarks.serve_throughput --json BENCH_serve.json

``--json PATH`` additionally writes the machine-readable record (one
entry per mode: wall-clock, req/s, dispatch/host-sync/round counts,
idle vs covered syncs, pool bytes) so CI can archive the perf
trajectory across PRs.
"""
from __future__ import annotations

from repro.core import batch, ladder, telemetry
from repro.core import bitset, frontier
from repro.serve.twscheduler import TwScheduler


def _counters(tr) -> dict:
    """The legacy-shaped counter dict for one measurement's tracker."""
    return {k: int(tr[k]) for k in telemetry.LEGACY_KEYS}

from .common import Timer, emit, get_instance

# the acceptance stream: 8 mixed Table-1 instances (small and mid blocks
# interleaved so lanes genuinely overlap requests of different depths)
STREAM = ["myciel3", "petersen", "queen5_5", "desargues",
          "myciel4", "petersen", "myciel3", "queen5_5"]
# CI-sized: small blocks only (plan_capacity stays well under DEFAULT_CAP,
# so the footprint cut is visible) and a 4-lane pool — the vmapped lane
# program compiles slowly on CPU (ROADMAP: TPU-vs-CPU compile note)
STREAM_QUICK = ["myciel3", "petersen", "myciel3", "petersen",
                "myciel3", "petersen", "myciel3", "petersen"]
STREAM_FULL = STREAM + ["queen6_6", "mcgee", "dyck", "myciel4"]


def run(full: bool = False, quick: bool = False, lanes: int = 8,
        block: int = 1 << 10, json_path: str = None):
    keys = STREAM_FULL if full else (STREAM_QUICK if quick else STREAM)
    gs = [get_instance(k) for k in keys]
    records = []

    header = (f"{'mode':<14} {'time_s':>8} {'req_s':>8} {'dispatches':>10} "
              f"{'host_syncs':>10} {'pool_MiB':>9}")
    print(header, flush=True)
    rows = {}

    # per-request baseline: fixed worst-case cap, one solve per request
    # (each mode gets a fresh detached tracker — isolated measurement)
    tr_seq = telemetry.Tracker()
    with Timer() as t_seq:
        seq = [ladder.solve(g, cap=batch.DEFAULT_CAP, block=block,
                            tracker=tr_seq)
               for g in gs]
    n_max = max(g.n for g in gs)
    seq_pool = frontier.frontier_bytes(batch.DEFAULT_CAP,
                                       bitset.n_words(n_max))
    rows["sequential"] = (t_seq.seconds, _counters(tr_seq), seq_pool, seq)

    # the service: continuous batching + plan_capacity-sized lane pool
    tr_srv = telemetry.Tracker()
    sched = TwScheduler(lanes=lanes, block=block, tracker=tr_srv)
    rids = [sched.submit(g) for g in gs]
    with Timer() as t_srv:
        done = sched.run()
    srv = [done[r] for r in rids]
    srv_pool = sched.pool_bytes()
    rows[f"service={lanes}"] = (t_srv.seconds, _counters(tr_srv),
                                srv_pool, srv)

    for mode, (secs, c, pool, results) in rows.items():
        print(f"{mode:<14} {secs:>8.2f} "
              f"{len(gs) / max(secs, 1e-9):>8.2f} {c['dispatches']:>10} "
              f"{c['host_syncs']:>10} {pool / 2**20:>9.2f}", flush=True)
        emit(f"serve_throughput/{mode}", secs,
             f"req_s={len(gs) / max(secs, 1e-9):.2f};"
             f"dispatches={c['dispatches']};host_syncs={c['host_syncs']};"
             f"pool_bytes={pool}")
        records.append(dict(mode=mode, shards=1, wall_s=secs,
                            req_s=len(gs) / max(secs, 1e-9),
                            dispatches=c["dispatches"],
                            host_syncs=c["host_syncs"], pool_bytes=pool))

    # parity: the service is pure scheduling — every request's result is
    # bit-identical to its solo solve
    for key, a, b in zip(keys, seq, srv):
        assert (a.width, a.exact, a.expanded, a.lb, a.ub) == \
            (b.width, b.exact, b.expanded, b.lb, b.ub), (key, a, b)

    (ts, cs, _, _), (tm, cm, pool_m, _) = \
        rows["sequential"], rows[f"service={lanes}"]
    d_ratio = cs["dispatches"] / max(cm["dispatches"], 1)
    assert cm["dispatches"] < cs["dispatches"], \
        "service must batch rungs into fewer dispatches"
    print(f"-> service: {d_ratio:.1f}x fewer dispatches, "
          f"{ts / max(tm, 1e-9):.2f}x wall-clock, "
          f"{len(gs) / max(tm, 1e-9):.2f} req/s", flush=True)
    emit("serve_throughput/summary", tm,
         f"dispatch_reduction={d_ratio:.2f}x;"
         f"speedup={ts / max(tm, 1e-9):.2f}x")

    records.append(run_overlap(keys, gs, seq, lanes=lanes, block=block))
    records.extend(run_pipeline(keys, gs, seq, lanes=lanes, block=block))
    records.extend(run_shards(lanes=lanes, block=block, quick=quick))

    if json_path:
        import json as json_lib
        with open(json_path, "w") as f:
            json_lib.dump({"bench": "serve_throughput", "stream": keys,
                           "lanes": lanes, "modes": records}, f, indent=2)
        print(f"-> wrote {json_path}", flush=True)
    return rows


def run_overlap(keys, gs, seq, *, lanes: int, block: int):
    """ISSUE 5's acceptance evidence: the async scheduler admits a
    mid-flight burst without waiting for pool idle, in fewer scheduler
    rounds than the blocking two-phase pattern, with per-request results
    (incl. the per_k reassembled from streamed events) bit-identical to
    sequential ``ladder.solve``."""
    # keep the early phase below the pool width so the mid-flight burst
    # has free slots to land in (a full pool admits FIFO as slots free —
    # correct, but the next-dispatch evidence needs free lanes)
    half = min(max(1, len(gs) // 2), max(1, lanes // 2))
    early, late = list(zip(keys, gs))[:half], list(zip(keys, gs))[half:]
    free = max(0, lanes - half)

    # blocking two-phase baseline: drain to idle, then serve the burst
    blocking = TwScheduler(lanes=lanes, block=block)
    b_rids = [blocking.submit(g) for _k, g in early]
    blocking.run()
    b_rids += [blocking.submit(g) for _k, g in late]
    blocking.run()

    # async overlap: the burst lands while dispatch 1 is in flight and is
    # admitted immediately (host bookkeeping under the flying device)
    tr = telemetry.Tracker()
    overlap = TwScheduler(lanes=lanes, block=block, tracker=tr)
    events = {}

    def submit(g):
        evs = []
        rid = overlap.submit(g, on_event=evs.append)
        events[rid] = evs
        return rid

    with Timer() as t_async:
        rids = [submit(g) for _k, g in early]
        launched = overlap.launch()
        rids += [submit(g) for _k, g in late]     # mid-flight arrivals
        overlap.poll_admissions()
        if launched:
            overlap.sync()
        done = overlap.run()
    c = _counters(tr)

    late_adm = [next(e["round"] for e in events[r] if e["event"] ==
                     "admitted") for r in rids[half:]]
    mode = f"async={lanes}"
    print(f"{mode:<14} {t_async.seconds:>8.2f} "
          f"{len(gs) / max(t_async.seconds, 1e-9):>8.2f} "
          f"{c['dispatches']:>10} {c['host_syncs']:>10} "
          f"{overlap.pool_bytes() / 2**20:>9.2f}", flush=True)
    print(f"-> overlap: late burst admitted at round(s) {late_adm} while "
          f"round 1 was in flight; {overlap.rounds} rounds vs "
          f"{blocking.rounds} blocking two-phase rounds", flush=True)
    # the burst lands in the free lanes for the NEXT dispatch (round 2),
    # never waiting for the pool to go idle; past the free lanes it
    # queues FIFO behind them as slots recycle
    assert all(r <= 2 for r in late_adm[:free]), \
        "mid-flight arrivals must be admitted for the next dispatch"
    assert overlap.rounds < blocking.rounds, \
        "overlap must beat waiting for pool idle"

    # parity incl. the streamed per_k deltas
    for key, ref, rid in zip(keys, seq, rids):
        res = done[rid]
        assert (ref.width, ref.exact, ref.expanded, ref.per_k) == \
            (res.width, res.exact, res.expanded, res.per_k), (key, ref, res)
        streamed = {}
        for e in events[rid]:
            if e["event"] == "rung_decided":
                streamed.setdefault(e["block"], {})[e["k"]] = {
                    "feasible": e["feasible"], "inexact": e["inexact"],
                    "expanded": e["expanded"]}
        searched = {blk: pk for blk, pk in res.per_k.items() if pk}
        assert streamed == searched, (key, streamed, searched)
    emit("serve_throughput/async_overlap", t_async.seconds,
         f"rounds={overlap.rounds};blocking_rounds={blocking.rounds};"
         f"late_admit_rounds={'+'.join(map(str, late_adm))};"
         f"dispatches={c['dispatches']}")
    return dict(mode=mode, shards=1, wall_s=t_async.seconds,
                req_s=len(gs) / max(t_async.seconds, 1e-9),
                dispatches=c["dispatches"], host_syncs=c["host_syncs"],
                rounds=overlap.rounds, blocking_rounds=blocking.rounds,
                pool_bytes=overlap.pool_bytes())


def run_pipeline(keys, gs, seq, *, lanes: int, block: int):
    """ISSUE 6's acceptance evidence: depth-2 pipelined dispatch shows
    fewer idle host-sync gaps than depth-1 serving of the same stream
    (every depth-2 sync past the first finds the next round already in
    flight), with per-request results bit-identical to depth 1 and to
    sequential ``ladder.solve``."""
    records, stats = [], {}
    for depth in (1, 2):
        tr = telemetry.Tracker()
        sched = TwScheduler(lanes=lanes, block=block, pipeline=depth,
                            tracker=tr)
        rids = [sched.submit(g) for g in gs]
        with Timer() as t:
            done = sched.run()
        c = _counters(tr)
        for key, ref, rid in zip(keys, seq, rids):
            res = done[rid]
            assert (ref.width, ref.exact, ref.expanded, ref.per_k) == \
                (res.width, res.exact, res.expanded, res.per_k), \
                (key, ref, res)
        mode = f"pipeline={depth}"
        print(f"{mode:<14} {t.seconds:>8.2f} "
              f"{len(gs) / max(t.seconds, 1e-9):>8.2f} "
              f"{c['dispatches']:>10} {c['host_syncs']:>10} "
              f"{sched.pool_bytes() / 2**20:>9.2f}", flush=True)
        emit(f"serve_throughput/{mode}", t.seconds,
             f"req_s={len(gs) / max(t.seconds, 1e-9):.2f};"
             f"dispatches={c['dispatches']};host_syncs={c['host_syncs']};"
             f"rounds={sched.rounds};idle_syncs={sched.idle_syncs};"
             f"covered_syncs={sched.covered_syncs}")
        stats[depth] = (sched.idle_syncs, sched.covered_syncs)
        records.append(dict(mode=mode, shards=1, wall_s=t.seconds,
                            req_s=len(gs) / max(t.seconds, 1e-9),
                            dispatches=c["dispatches"],
                            host_syncs=c["host_syncs"],
                            rounds=sched.rounds,
                            idle_syncs=sched.idle_syncs,
                            covered_syncs=sched.covered_syncs,
                            pool_bytes=sched.pool_bytes()))
    print(f"-> pipeline: depth 2 ran {stats[2][0]} idle / {stats[2][1]} "
          f"covered host syncs vs depth 1's {stats[1][0]} idle "
          f"(device kept busy across the sync gap)", flush=True)
    assert stats[2][1] > 0, "depth 2 must cover syncs with queued rounds"
    assert stats[2][0] < stats[1][0], \
        "depth 2 must show fewer idle host-sync gaps than depth 1"
    return records


def run_shards(*, lanes: int, block: int, quick: bool = False):
    """ISSUE 7's acceptance evidence: one heavy request submitted with
    ``shards=4`` — its rungs decided by 4-way sharded dispatches
    (``core.shard``: owner-hash frontier split + work donation) and its
    ladder climbing 4 rungs per round from its 4-slot entitlement —
    finishes in measurably fewer scheduler rounds than the identical
    request with ``shards=1``, while the concurrent small requests keep
    completing.  Every request's result is asserted bit-identical to
    sequential ``ladder.solve`` in both runs."""
    heavy_key = "myciel4" if quick else "queen5_5"
    heavy = get_instance(heavy_key)
    small_keys = ["myciel3", "petersen", "myciel3"]
    smalls = [get_instance(k) for k in small_keys]
    ref_h = ladder.solve(heavy, block=block)
    ref_s = [ladder.solve(g, block=block) for g in smalls]

    records, done_rounds = [], {}
    for s in (1, 4):
        tr = telemetry.Tracker()
        sched = TwScheduler(lanes=lanes, block=block, tracker=tr)
        evs = []
        with Timer() as t:
            rid_h = sched.submit(heavy, shards=s, on_event=evs.append)
            rids = [sched.submit(g) for g in smalls]
            done = sched.run()
        c = _counters(tr)
        done_rounds[s] = next(e["rounds"] for e in evs
                              if e["event"] == "done")
        rh = done[rid_h]
        assert (rh.width, rh.exact, rh.expanded, rh.per_k) == \
            (ref_h.width, ref_h.exact, ref_h.expanded, ref_h.per_k), \
            (heavy_key, s, rh, ref_h)
        for key, rid, ref in zip(small_keys, rids, ref_s):
            res = done[rid]
            assert (res.width, res.exact, res.expanded) == \
                (ref.width, ref.exact, ref.expanded), (key, s, res, ref)
        mode = f"shards={s}"
        print(f"{mode:<14} {t.seconds:>8.2f} "
              f"{(1 + len(smalls)) / max(t.seconds, 1e-9):>8.2f} "
              f"{c['dispatches']:>10} {c['host_syncs']:>10} "
              f"{sched.pool_bytes() / 2**20:>9.2f}", flush=True)
        emit(f"serve_throughput/{mode}", t.seconds,
             f"heavy={heavy_key};heavy_done_round={done_rounds[s]};"
             f"rounds={sched.rounds};dispatches={c['dispatches']};"
             f"donations={c['shard_donations']};"
             f"donated_rows={c['shard_donated_rows']};"
             f"idle_steps={c['shard_idle_steps']};"
             f"peak_occupancy={c['shard_peak_occupancy']}")
        records.append(dict(
            mode=mode, shards=s, wall_s=t.seconds, heavy=heavy_key,
            heavy_done_round=done_rounds[s], rounds=sched.rounds,
            dispatches=c["dispatches"], host_syncs=c["host_syncs"],
            shard_donations=c["shard_donations"],
            shard_donated_rows=c["shard_donated_rows"],
            shard_idle_steps=c["shard_idle_steps"],
            shard_peak_occupancy=c["shard_peak_occupancy"],
            pool_bytes=sched.pool_bytes()))
    print(f"-> shards: heavy ({heavy_key}) done at round "
          f"{done_rounds[4]} sharded vs {done_rounds[1]} unsharded; "
          f"smalls completed in both runs", flush=True)
    assert done_rounds[4] < done_rounds[1], \
        "sharded heavy request must finish in fewer scheduler rounds"
    return records


if __name__ == "__main__":
    import sys
    lanes = 8
    if "--lanes" in sys.argv:
        lanes = int(sys.argv[sys.argv.index("--lanes") + 1])
    if "--quick" in sys.argv and "--lanes" not in sys.argv:
        lanes = 4
    json_path = None
    if "--json" in sys.argv:
        json_path = sys.argv[sys.argv.index("--json") + 1]
    run(full="--full" in sys.argv, quick="--quick" in sys.argv,
        lanes=lanes, json_path=json_path)
