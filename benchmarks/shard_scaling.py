"""Intra-request scale-out bench: sequential solve vs sharded solve.

ISSUE 7's perf trajectory: for each of the heavier Table 1 instances,
run the fused-engine ladder sequentially (``ladder.solve``) and then
with the frontier split across S vmapped shard lanes
(``ladder.solve(shards=S)`` -> ``core.shard``: owner-hash routing +
per-level work donation).  Every sharded run is asserted bit-identical
to the sequential baseline — width, exactness, states expanded, and the
per-rung feasibility trace — so the table measures pure partitioning
cost/benefit, never a search-quality trade.

On CPU the vmapped shard lanes execute serially, so wall-clock speedup
is flat-to-negative here; the numbers that carry are the shard-health
counters (donations, donated rows, idle shard-steps, peak per-shard
occupancy — a per-measurement ``telemetry.Tracker``) showing the rebalancer
keeping the lanes busy.  Wall-clock becomes meaningful on real
accelerators where the lanes map onto hardware parallelism.

    python -m benchmarks.shard_scaling                # fast suite
    python -m benchmarks.shard_scaling --quick        # CI-sized suite
    python -m benchmarks.shard_scaling --full
    python -m benchmarks.shard_scaling --json BENCH_shard.json

``--json PATH`` additionally writes the machine-readable records so CI
can archive the trajectory next to ``BENCH_serve.json``.
"""
from __future__ import annotations

from repro.core import ladder, telemetry

from .common import Timer, emit, get_instance

# Heavier Table 1 instances: sharding targets the requests whose rungs
# dominate a pool, not the toys.
SUITE = [("myciel4", 10), ("queen5_5", 18)]
SUITE_QUICK = [("myciel3", 5), ("petersen", 4)]
SUITE_FULL = SUITE + [("queen6_6", 25), ("dyck", 7)]

SHARDS = (2, 4)

SHARD_KEYS = ("shard_donations", "shard_donated_rows",
              "shard_idle_steps", "shard_peak_occupancy")


def run(full: bool = False, quick: bool = False, block: int = 1 << 10,
        json_path: str = None):
    suite = SUITE_FULL if full else (SUITE_QUICK if quick else SUITE)
    records = []
    header = (f"{'instance':<12} {'shards':>6} {'tw':>3} {'time_s':>8} "
              f"{'speedup':>8} {'donations':>9} {'don_rows':>8} "
              f"{'idle':>6} {'peak_occ':>8}")
    print(header, flush=True)
    for key, want in suite:
        g = get_instance(key)
        tr0 = telemetry.Tracker()
        with Timer() as t0:
            ref = ladder.solve(g, block=block, tracker=tr0)
        c0 = {k: int(tr0[k]) for k in telemetry.LEGACY_KEYS}
        assert want is None or ref.width == want, (key, ref.width, want)
        print(f"{key:<12} {1:>6} {ref.width:>3} {t0.seconds:>8.2f} "
              f"{'1.00':>8} {'-':>9} {'-':>8} {'-':>6} {'-':>8}",
              flush=True)
        emit(f"shard_scaling/{key}/shards1", t0.seconds,
             f"tw={ref.width};dispatches={c0['dispatches']}")
        records.append(dict(instance=key, shards=1, tw=ref.width,
                            wall_s=t0.seconds, speedup=1.0,
                            dispatches=c0["dispatches"]))
        for s in SHARDS:
            tr = telemetry.Tracker()
            with Timer() as t:
                res = ladder.solve(g, block=block, shards=s, tracker=tr)
            c = {k: int(tr[k]) for k in telemetry.LEGACY_KEYS}
            # bit-for-bit parity with the sequential ladder: sharding
            # repartitions the frontier, it never re-expands or prunes
            # differently
            assert (res.width, res.exact, res.expanded, res.per_k) == \
                (ref.width, ref.exact, ref.expanded, ref.per_k), \
                (key, s, res, ref)
            speedup = t0.seconds / max(t.seconds, 1e-9)
            health = ";".join(f"{k}={c[k]}" for k in SHARD_KEYS)
            print(f"{key:<12} {s:>6} {res.width:>3} {t.seconds:>8.2f} "
                  f"{speedup:>8.2f} {c['shard_donations']:>9} "
                  f"{c['shard_donated_rows']:>8} "
                  f"{c['shard_idle_steps']:>6} "
                  f"{c['shard_peak_occupancy']:>8}", flush=True)
            emit(f"shard_scaling/{key}/shards{s}", t.seconds,
                 f"tw={res.width};speedup={speedup:.2f}x;{health};"
                 f"parity=exact")
            records.append(dict(
                instance=key, shards=s, tw=res.width, wall_s=t.seconds,
                speedup=speedup, dispatches=c["dispatches"],
                **{k: c[k] for k in SHARD_KEYS}))
    if json_path:
        import json as json_lib
        with open(json_path, "w") as f:
            json_lib.dump({"bench": "shard_scaling",
                           "shards": [1, *SHARDS],
                           "records": records}, f, indent=2)
        print(f"-> wrote {json_path}", flush=True)
    return records


if __name__ == "__main__":
    import sys
    json_path = None
    if "--json" in sys.argv:
        json_path = sys.argv[sys.argv.index("--json") + 1]
    run(full="--full" in sys.argv, quick="--quick" in sys.argv,
        json_path=json_path)
