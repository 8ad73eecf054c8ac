"""Treewidth solver CLI (the paper's workload).

    python -m repro.launch.solve --graph queen5_5
    python -m repro.launch.solve --graph myciel4 --mode bloom --mmw
    python -m repro.launch.solve --graph myciel3 --backend pallas --simplicial
    python -m repro.launch.solve --graph queen6_6 --distributed --devices 8
    python -m repro.launch.solve --graph myciel4 --batch 4
    python -m repro.launch.solve --graph queen6_6 --shards 4
    python -m repro.launch.solve --dimacs path/to/graph.gr

``--batch N`` runs the iterative-deepening ladder speculatively: each
dispatch decides N consecutive widths through the multi-lane engine
(``repro.core.batch``), and the smallest feasible one wins — same
results, fewer dispatches.

``--shards S`` scales one rung *out* instead: the frontier is split
across S vmapped shard lanes (owner-hash routing + work donation,
``repro.core.shard``), multiplying per-level throughput and aggregate
frontier capacity for a single heavy instance — results bit-identical
to the sequential ladder.

``--backend`` selects the op implementations through the registry
(``repro.core.backend``): "jax" reference or the fused Pallas wavefront
kernel ("pallas"; interpret mode off-TPU).  Unsupported combinations are
rejected here with a capability error before anything is traced.

Every path runs the deepening ladder of ``repro.core.ladder``; each rung
is one dispatch of the lane program (``--shards``: the sharded program,
``--distributed``: the mesh program).

``--cap`` defaults to auto-sizing (``repro.core.batch.plan_capacity``).
To serve a *stream* of solve requests through one lane pool instead of
solving one instance, see ``python -m repro.launch.twserve``.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="",
                    help="generator name (see core.graph.REGISTRY)")
    ap.add_argument("--dimacs", default="", help="DIMACS/.gr file")
    ap.add_argument("--cap", type=int, default=None,
                    help="frontier rows per level (power of two). Default: "
                         "auto — repro.core.batch.plan_capacity right-sizes "
                         "the buffer per preprocessed block (drop-free "
                         "state bound, clamped to 2^17) instead of the old "
                         "fixed 2^18; results are bit-identical, small "
                         "blocks just stop paying the worst-case footprint. "
                         "--distributed still defaults to 2^18 (sharded "
                         "caps are split across devices, not planned)")
    ap.add_argument("--block", type=int, default=1 << 10)
    ap.add_argument("--mode", default="sort", choices=["sort", "bloom"])
    ap.add_argument("--batch", type=int, default=1, metavar="LANES",
                    help="speculative deepening width: decide k..k+LANES-1 "
                         "concurrently in one multi-lane dispatch "
                         "(core.batch; results bit-identical to "
                         "--batch 1). Default 1")
    ap.add_argument("--shards", type=int, default=1, metavar="S",
                    help="intra-request scale-out: split each rung's "
                         "frontier across S vmapped shard lanes with "
                         "work donation (core.shard; results "
                         "bit-identical to --shards 1). Default 1")
    ap.add_argument("--donate-ratio", type=float, default=None,
                    help="sharded work-donation trigger: rebalance when "
                         "the max shard exceeds ratio x mean occupancy "
                         "(default core.shard.DEFAULT_DONATE_RATIO)")
    ap.add_argument("--mmw", action="store_true")
    ap.add_argument("--simplicial", action="store_true",
                    help="enable simplicial-vertex branch collapse")
    ap.add_argument("--backend", default="jax", choices=["jax", "pallas"],
                    help="op implementations (repro.core.backend registry): "
                         "jax reference or fused pallas kernels")
    ap.add_argument("--schedule", default="doubling",
                    choices=["doubling", "while", "linear", "matmul"])
    ap.add_argument("--no-paths", action="store_true")
    ap.add_argument("--no-clique", action="store_true")
    ap.add_argument("--no-preprocess", action="store_true")
    ap.add_argument("--reconstruct", action="store_true")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (set before jax init)")
    ap.add_argument("--heuristics", type=int, default=0, metavar="N",
                    help="anytime bounds-improver rounds applied at plan "
                         "time (randomized elimination sweeps + contraction "
                         "lower bounds, DESIGN.md §15); tightens the ladder, "
                         "never the verdict")
    ap.add_argument("--seed", type=int, default=0,
                    help="pins every heuristic draw (clique restarts, "
                         "randomized sweeps, contractions)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro.core import backend as backend_lib
    from repro.core import distributed as dist_lib
    from repro.core import graph as graph_lib
    from repro.core import ladder, solver

    # fail on unsupported backend/flag combos here, with an actionable
    # message, instead of deep inside a jit
    try:
        backend_lib.validate(args.backend, mode=args.mode,
                             schedule=args.schedule, use_mmw=args.mmw,
                             use_simplicial=args.simplicial,
                             lanes=args.batch, shards=args.shards)
    except backend_lib.BackendCapabilityError as e:
        print(f"[solve] unsupported configuration: {e}", file=sys.stderr)
        return 2

    if args.dimacs:
        g = graph_lib.read_dimacs(args.dimacs)
    elif args.graph in graph_lib.REGISTRY:
        g = graph_lib.REGISTRY[args.graph]()
    else:
        print(f"unknown graph {args.graph!r}; known: "
              f"{sorted(graph_lib.REGISTRY)}")
        return 2

    print(f"[solve] {g.name}: n={g.n} m={g.n_edges}", flush=True)
    if args.distributed and args.batch > 1:
        print("[solve] --batch applies to the single-device solver only; "
              "ignoring it under --distributed", file=sys.stderr)
    if args.distributed:
        mesh = dist_lib.make_solver_mesh()
        cap = args.cap if args.cap is not None else 1 << 18
        kw = {}
        if args.donate_ratio is not None:
            kw["donate_ratio"] = args.donate_ratio
        res = ladder.solve_distributed(
            g, mesh, cap_local=cap // max(1, mesh.devices.size),
            block=args.block, use_mmw=args.mmw,
            use_simplicial=args.simplicial,
            schedule=args.schedule, backend=args.backend,
            use_clique=not args.no_clique, use_paths=not args.no_paths,
            use_preprocess=not args.no_preprocess, verbose=args.verbose,
            **kw)
    else:
        res = ladder.solve(
            g, cap=args.cap, block=args.block, mode=args.mode,
            use_mmw=args.mmw, backend=args.backend, schedule=args.schedule,
            use_simplicial=args.simplicial,
            use_clique=not args.no_clique, use_paths=not args.no_paths,
            use_preprocess=not args.no_preprocess,
            reconstruct=args.reconstruct, verbose=args.verbose,
            lanes=args.batch, shards=args.shards,
            donate_ratio=args.donate_ratio,
            heuristics=args.heuristics, seed=args.seed)

    print(f"[solve] treewidth={res.width} exact={res.exact} "
          f"lb={res.lb} ub={res.ub} states_expanded={res.expanded} "
          f"time={res.time_sec:.2f}s")
    if res.order is not None:
        width = solver.order_width(g, res.order)
        print(f"[solve] elimination order verified: width={width}")
    return 0


if __name__ == "__main__":
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
