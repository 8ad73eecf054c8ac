"""Host-sync microbench: backend (jax vs pallas) x lanes (sequential vs
speculative deepening) x shards (scale-out).

The paper's §3 design point is that the Held-Karp frontier never leaves the
GPU; the cost of not doing that is kernel-dispatch serialisation.  Every
rung of ``ladder.solve`` is one dispatch of a device-resident program, so
this bench reports, on the Table 1 instances, the wall-clock, jitted-program
dispatches and blocking device→host transfers of the full iterative-
deepening solve per backend x lanes x shards combination (counted by a
per-measurement ``repro.core.telemetry.Tracker`` — a detached scope, so
concurrent process-global accounting never leaks into a row).

The backend column tracks the fused pallas wavefront kernel against the
jax reference composition.  The lanes column runs speculative deepening
(``ladder.solve(lanes=4)`` -> ``core.batch``): one multi-lane dispatch
per ladder window instead of one per k; ``benchmarks/batch_throughput.py``
covers the cross-instance ``solve_many`` axis.  The shards column runs
intra-request scale-out (``ladder.solve(shards=2)`` -> ``core.shard``):
the frontier split across vmapped shard lanes with work donation — the
shard-health counters (donations, donated rows, idle shard-steps, peak
occupancy) land in the same tracker scope.  On CPU the pallas rows run
in interpret mode, so their absolute times measure the interpreter, not
the kernel — the dispatch/sync counts and the bit-for-bit width/
expanded parity asserts are what carry; wall-clock becomes meaningful on
real TPU hardware.

    python -m benchmarks.engine_sync             # fast suite
    python -m benchmarks.engine_sync --quick     # CI-sized suite
    python -m benchmarks.engine_sync --full
    python -m benchmarks.engine_sync --no-pallas # jax rows only
"""
from __future__ import annotations

from repro.core import ladder, telemetry

from .common import SUITE_FAST, SUITE_FULL, Timer, emit, get_instance

SUITE_QUICK = [("myciel3", 5), ("petersen", 4), ("desargues", 6)]

# (backend, lanes, shards) rows per instance.  The lanes=4 row runs the
# ladder through the multi-lane speculative-deepening path (core.batch);
# the shards=2 row through the sharded scale-out path (core.shard) — both
# extra columns of the dispatch/sync accounting, and both must stay
# bit-identical to the sequential row.
COMBOS = [("jax", 1, 1), ("jax", 4, 1), ("jax", 1, 2), ("pallas", 1, 1)]

SHARD_KEYS = ("shard_donations", "shard_donated_rows",
              "shard_idle_steps", "shard_peak_occupancy")


def run(full: bool = False, quick: bool = False, pallas: bool = True,
        cap: int = 1 << 18, block: int = 1 << 10):
    suite = SUITE_FULL if full else (SUITE_QUICK if quick else SUITE_FAST)
    combos = [c for c in COMBOS if pallas or c[0] != "pallas"]
    rows = []
    header = (f"{'instance':<12} {'backend':<7} {'lanes':>5} "
              f"{'shards':>6} {'tw':>3} {'time_s':>8} {'dispatches':>10} "
              f"{'host_syncs':>10}")
    print(header, flush=True)
    for key, want in suite:
        g = get_instance(key)
        per_combo = {}
        for backend, lanes, shards in combos:
            # fresh detached tracker per measurement: isolates this run's
            # counters from the process-global accounting
            tr = telemetry.Tracker()
            with Timer() as t:
                res = ladder.solve(g, cap=cap, block=block, backend=backend,
                                   schedule="doubling", lanes=lanes,
                                   shards=shards, tracker=tr)
            c = {k: int(tr[k]) for k in telemetry.LEGACY_KEYS}
            ok = (want is None) or (res.width == want)
            per_combo[(backend, lanes, shards)] = (res, c, t.seconds, ok)
            rows.append((key, backend, lanes, shards, res.width,
                         t.seconds, c["dispatches"], c["host_syncs"], ok))
            print(f"{key:<12} {backend:<7} {lanes:>5} "
                  f"{shards:>6} {res.width:>3} {t.seconds:>8.2f} "
                  f"{c['dispatches']:>10} {c['host_syncs']:>10}",
                  flush=True)
            emit(f"engine_sync/{key}/{backend}/lanes{lanes}"
                 f"/shards{shards}",
                 t.seconds,
                 f"tw={res.width};dispatches={c['dispatches']};"
                 f"host_syncs={c['host_syncs']};expected_ok={ok}")
        # parity across every combo: same width, same states expanded
        # (speculative lanes discard rungs above the first feasible one
        # and shards repartition without re-expanding, so even the
        # lanes=4 and shards=2 rows must match exactly)
        base, *rest = [per_combo[c][0] for c in combos]
        for r in rest:
            assert r.width == base.width, (key, r.width, base.width)
            assert r.expanded == base.expanded, \
                (key, r.expanded, base.expanded)
        (rf, cf, tf, _) = per_combo[("jax", 1, 1)]
        (rb, cb, tb, _) = per_combo[("jax", 4, 1)]
        emit(f"engine_sync/{key}/batch_summary", tb,
             f"seq_dispatches={cf['dispatches']};"
             f"lanes4_dispatches={cb['dispatches']};parity=exact")
        (rs, cs, ts, _) = per_combo[("jax", 1, 2)]
        shard_health = ";".join(f"{k}={cs[k]}" for k in SHARD_KEYS)
        emit(f"engine_sync/{key}/shard_summary", ts,
             f"seq_s={tf:.3f};shards2_s={ts:.3f};{shard_health};"
             f"parity=exact")
        print(f"{key:<12} -> shards=2: "
              f"{cs['shard_donations']} donations "
              f"({cs['shard_donated_rows']} rows), "
              f"{cs['shard_idle_steps']} idle shard-steps, "
              f"peak occupancy {cs['shard_peak_occupancy']}", flush=True)
        if ("pallas", 1, 1) in per_combo:
            (rp, cp, tp, _) = per_combo[("pallas", 1, 1)]
            emit(f"engine_sync/{key}/backend_summary", tp,
                 f"jax_s={tf:.3f};pallas_s={tp:.3f};parity=exact")
    return rows


if __name__ == "__main__":
    import sys
    run(full="--full" in sys.argv, quick="--quick" in sys.argv,
        pallas="--no-pallas" not in sys.argv)
