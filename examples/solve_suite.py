"""End-to-end driver (the paper's workload): solve a benchmark suite and
print a Table-1 style report.

    PYTHONPATH=src python examples/solve_suite.py [--full] [--batch [LANES]]

``--batch`` solves the whole suite through the multi-lane engine
(``repro.core.ladder.solve_many``): instead of one dispatch per
(instance, k), every scheduler round packs the current deepening rung of
every unfinished instance into shared multi-lane dispatches.  Same
widths/exactness, far fewer dispatches — the report prints both counters.
"""
import sys
import time

from repro.core import batch, engine, graph, ladder

SUITE = [("myciel3", 5), ("petersen", 4), ("queen5_5", 18),
         ("queen6_6", 25), ("myciel4", 10), ("desargues", 6)]
if "--full" in sys.argv:
    SUITE += [("mcgee", 7), ("dyck", 7), ("queen7_7", 35)]


def _batch_lanes(argv):
    """0 = sequential; --batch alone = default lanes; --batch N = N."""
    if "--batch" not in argv:
        return 0
    i = argv.index("--batch")
    if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
        return batch.DEFAULT_MAX_LANES
    try:
        lanes = int(argv[i + 1])
    except ValueError:
        sys.exit(f"--batch expects a lane count, got {argv[i + 1]!r}")
    if lanes < 1:
        sys.exit(f"--batch expects a lane count >= 1, got {lanes}")
    return lanes


def main(argv):
    lanes = _batch_lanes(argv)
    kw = dict(cap=1 << 18, block=1 << 10)
    names = [key for key, _ in SUITE]
    gs = [graph.REGISTRY[key]() for key in names]

    print(f"{'name':<12} {'|V|':>4} {'tw':>4} {'exact':>6} "
          f"{'time(s)':>8} {'Exp':>10}")
    engine.reset_counters()
    t0 = time.time()
    if lanes:
        results = ladder.solve_many(gs, lanes=lanes, **kw)
        times = [None] * len(gs)       # lanes overlap; per-instance wall
        total_t = time.time() - t0     # time is the suite wall-clock
    else:
        results, times = [], []
        for g in gs:
            t1 = time.time()
            results.append(ladder.solve(g, **kw))
            times.append(time.time() - t1)
        total_t = time.time() - t0
    counters = dict(engine.COUNTERS)

    total_exp = 0
    for (key, want), g, res, dt in zip(SUITE, gs, results, times):
        total_exp += res.expanded
        flag = "" if res.width == want else f"  (expected {want}!)"
        tcol = f"{dt:>8.2f}" if dt is not None else f"{'—':>8}"
        print(f"{key:<12} {g.n:>4} {res.width:>4} {str(res.exact):>6} "
              f"{tcol} {res.expanded:>10}{flag}")
    mode = f"solve_many lanes={lanes}" if lanes else "sequential"
    print(f"\ntotal ({mode}): {total_t:.1f}s, {total_exp} states "
          f"({total_exp / max(total_t, 1e-9):.0f} states/s), "
          f"{counters['dispatches']} dispatches, "
          f"{counters['host_syncs']} host syncs")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
