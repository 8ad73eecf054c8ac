"""Benchmark harness entry point: one section per paper table.

    PYTHONPATH=src python -m benchmarks.run            # fast suite
    PYTHONPATH=src python -m benchmarks.run --full     # adds heavy graphs
    PYTHONPATH=src python -m benchmarks.run --pallas   # adds kernel sweep

Output contract: ``name,us_per_call,derived`` CSV lines.
"""
from __future__ import annotations

import sys


def main() -> None:
    full = "--full" in sys.argv
    pallas = "--pallas" in sys.argv

    print("# table1: general benchmark (paper Table 1)", flush=True)
    from . import table1_general
    table1_general.run(full=full, json_path="BENCH_solver.json")

    print("# engine_sync: dispatches + syncs per backend x lanes x shards",
          flush=True)
    from . import engine_sync
    engine_sync.run(full=full)

    print("# batch_throughput: multi-lane engine vs sequential dispatches",
          flush=True)
    from . import batch_throughput
    batch_throughput.run(full=full)

    print("# serve_throughput: solve service (continuous batching) vs "
          "per-request solving", flush=True)
    from . import serve_throughput
    serve_throughput.run(full=full, quick=not full,
                         lanes=8 if full else 4)

    print("# serve_load: open-loop arrival trace vs the persistent "
          "service (submit->done latency percentiles)", flush=True)
    from . import serve_load
    serve_load.run(quick=not full)

    print("# cache_effect: content-addressed result cache (hit rate, "
          "warm-hit latency, bit-identity vs uncached)", flush=True)
    from . import cache_effect
    cache_effect.run(full=full, quick=not full,
                     json_path="BENCH_cache.json")

    print("# shard_scaling: intra-request scale-out (sharded frontier "
          "vs sequential)", flush=True)
    from . import shard_scaling
    shard_scaling.run(full=full, quick=not full)

    print("# bounds_quality: anytime heuristic bounds engine (rung "
          "reduction + ub-lb gap vs time)", flush=True)
    from . import bounds_quality
    bounds_quality.run(full=full, quick=not full)

    print("# table2: work-size x memory sweep (paper Tables 2/3)",
          flush=True)
    from . import table2_worksize
    table2_worksize.run(pallas=pallas)

    print("# table4: minor-min-width on/off (paper Tables 4/5)", flush=True)
    from . import table4_mmw
    table4_mmw.run()

    print("# table6: loop scheduling (paper Table 6)", flush=True)
    from . import table6_unnesting
    table6_unnesting.run()

    print("# simplicial: beyond-paper pruning (paper §5 future work)",
          flush=True)
    from . import table_simplicial
    table_simplicial.run()

    print("# lm: substrate microbench", flush=True)
    from . import lm_microbench
    lm_microbench.run()

    print("# roofline: dry-run derived terms (see EXPERIMENTS.md)",
          flush=True)
    try:
        from . import roofline
        roofline.main()
    except Exception as e:                      # noqa: BLE001
        print(f"roofline,0,unavailable ({e!r})")


if __name__ == "__main__":
    main()
