"""Table 1 analogue: general solver benchmark (time + states explored).

Paper Table 1 reports |V|, tw, GPU/CPU time, and states expanded per
instance.  The CPU-hosted JAX build plays the role of the paper's CPU
baseline; the Pallas kernel path (interpret mode here, native on TPU) is
also timed for reference.

    python -m benchmarks.table1_general                 # fast suite
    python -m benchmarks.table1_general --quick         # CI-sized suite
    python -m benchmarks.table1_general --full
    python -m benchmarks.table1_general --json BENCH_solver.json

``--json PATH`` writes the machine-readable per-instance records —
wall-clock, jitted-program dispatches and host syncs (from a detached
per-measurement ``telemetry.Tracker``), states expanded — so CI can
archive the solver-side perf trajectory next to ``BENCH_serve.json``
and ``BENCH_shard.json``.
"""
from __future__ import annotations

from repro.core import ladder, telemetry

from .common import SUITE_FAST, SUITE_FULL, Timer, emit, get_instance

SUITE_QUICK = [("myciel3", 5), ("petersen", 4), ("desargues", 6)]


def run(full: bool = False, quick: bool = False, cap: int = 1 << 18,
        block: int = 1 << 10, json_path: str = None):
    suite = SUITE_FULL if full else (SUITE_QUICK if quick else SUITE_FAST)
    rows, records = [], []
    for key, want in suite:
        g = get_instance(key)
        tr = telemetry.Tracker()
        with Timer() as t:
            res = ladder.solve(g, cap=cap, block=block, tracker=tr)
        ok = (want is None) or (res.width == want)
        rows.append((key, g.n, res.width, res.exact, res.expanded,
                     t.seconds, ok))
        emit(f"table1/{key}", t.seconds,
             f"n={g.n};tw={res.width};exact={res.exact};"
             f"exp={res.expanded};expected_ok={ok};"
             f"dispatches={int(tr['dispatches'])};"
             f"host_syncs={int(tr['host_syncs'])}")
        states_per_sec = res.expanded / max(t.seconds, 1e-9)
        emit(f"table1/{key}/throughput", 1.0 / max(states_per_sec, 1e-9),
             f"states_per_sec={states_per_sec:.0f}")
        records.append(dict(
            instance=key, n=int(g.n), tw=int(res.width),
            exact=bool(res.exact), expanded=int(res.expanded),
            wall_s=t.seconds, states_per_sec=states_per_sec,
            dispatches=int(tr["dispatches"]),
            host_syncs=int(tr["host_syncs"]), expected_ok=bool(ok)))
    if json_path:
        import json as json_lib
        with open(json_path, "w") as f:
            json_lib.dump({"bench": "table1_general",
                           "suite": [k for k, _w in suite],
                           "records": records}, f, indent=2)
        print(f"-> wrote {json_path}", flush=True)
    return rows


if __name__ == "__main__":
    import sys
    json_path = None
    if "--json" in sys.argv:
        json_path = sys.argv[sys.argv.index("--json") + 1]
    run(full="--full" in sys.argv, quick="--quick" in sys.argv,
        json_path=json_path)
