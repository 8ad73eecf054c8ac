"""Lane-batching throughput bench: sequential solve loop vs solve_many.

ISSUE 3's motivation quantified: the sequential suite issues one fused
dispatch per (instance, k) and the device idles between them; the
multi-lane engine (``repro.core.batch``) packs the unfinished instances'
current deepening rungs into shared dispatches.  For the suite this bench
reports wall-clock, dispatch and host-sync counts for

  * ``sequential`` — ``[ladder.solve(g) for g in suite]``
  * ``lanes=L``    — ``ladder.solve_many(suite, lanes=L)``
  * ``spec=S``     — per-instance speculative deepening
                     (``ladder.solve(g, lanes=S)``), the single-instance
                     counterpart

and asserts width/exactness parity between all of them (expanded parity
too — the default config has no padded-MMW caveat).  On CPU absolute
times measure XLA's CPU backend; the dispatch/sync reductions are the
portable signal (as with engine_sync, wall-clock becomes meaningful on
real TPU hardware).

    python -m benchmarks.batch_throughput              # fast suite
    python -m benchmarks.batch_throughput --quick      # CI-sized
    python -m benchmarks.batch_throughput --full
    python -m benchmarks.batch_throughput --lanes 16
"""
from __future__ import annotations

from repro.core import engine as engine_lib, ladder

from .common import SUITE_FAST, SUITE_FULL, Timer, emit, get_instance

SUITE_QUICK = [("myciel3", 5), ("petersen", 4), ("desargues", 6)]


def run(full: bool = False, quick: bool = False, lanes: int = 8,
        speculate: int = 4, cap: int = 1 << 18, block: int = 1 << 10):
    suite = SUITE_FULL if full else (SUITE_QUICK if quick else SUITE_FAST)
    keys = [k for k, _ in suite]
    gs = [get_instance(k) for k in keys]
    kw = dict(cap=cap, block=block)

    header = (f"{'mode':<16} {'time_s':>8} {'dispatches':>10} "
              f"{'host_syncs':>10} {'states':>10}")
    print(header, flush=True)
    rows = {}

    engine_lib.reset_counters()
    with Timer() as t_seq:
        seq = [ladder.solve(g, **kw) for g in gs]
    rows["sequential"] = (t_seq.seconds, dict(engine_lib.COUNTERS), seq)

    engine_lib.reset_counters()
    with Timer() as t_spec:
        spec = [ladder.solve(g, lanes=speculate, **kw) for g in gs]
    rows[f"spec={speculate}"] = (t_spec.seconds, dict(engine_lib.COUNTERS),
                                 spec)

    engine_lib.reset_counters()
    with Timer() as t_many:
        many = ladder.solve_many(gs, lanes=lanes, **kw)
    rows[f"lanes={lanes}"] = (t_many.seconds, dict(engine_lib.COUNTERS),
                              many)

    for mode, (secs, c, results) in rows.items():
        states = sum(r.expanded for r in results)
        print(f"{mode:<16} {secs:>8.2f} {c['dispatches']:>10} "
              f"{c['host_syncs']:>10} {states:>10}", flush=True)
        emit(f"batch_throughput/{mode}", secs,
             f"dispatches={c['dispatches']};host_syncs={c['host_syncs']};"
             f"states={states}")

    # parity across every mode: the batching axes are pure scheduling
    for mode in list(rows)[1:]:
        for key, a, b in zip(keys, seq, rows[mode][2]):
            assert (a.width, a.exact, a.expanded) == \
                (b.width, b.exact, b.expanded), (mode, key, a, b)

    (ts, cs, _), (tm, cm, _) = rows["sequential"], rows[f"lanes={lanes}"]
    d_ratio = cs["dispatches"] / max(cm["dispatches"], 1)
    print(f"-> solve_many: {d_ratio:.1f}x fewer dispatches, "
          f"{ts / max(tm, 1e-9):.2f}x wall-clock", flush=True)
    emit("batch_throughput/summary", tm,
         f"dispatch_reduction={d_ratio:.2f}x;"
         f"speedup={ts / max(tm, 1e-9):.2f}x")
    return rows


if __name__ == "__main__":
    import sys
    lanes = 8
    if "--lanes" in sys.argv:
        lanes = int(sys.argv[sys.argv.index("--lanes") + 1])
    run(full="--full" in sys.argv, quick="--quick" in sys.argv,
        lanes=lanes)
