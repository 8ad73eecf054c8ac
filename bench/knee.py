"""Find the knee of an open-loop mix: the highest arrival rate the pool
sustains without a growing backlog.  One process, one server, warmed
once; the rates run in the order given, each for ``--seconds`` with fresh
requests from its own seed, drained before the next, until one is not
sustained.

    python3 -m bench.knee --workload <open cell> --rates 1,2,4,8 \\
        --seconds 30 --seed 7

A rate is sustained when the requests of the window's last third waited
no longer, at the median, than twice those of its first third plus a
quarter second, and every answer came back within the minute after the
close.  The chosen rates go
into the mix files by hand, as numbers (PERF.md gives the sweep).  Prints
one JSON line per rate; needs the chip, like ``bench.run``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys


def sweep(workload: str, rates, seconds: float, seed: int) -> list:
    from bench import arrivals, run
    loaded = run.load_cell(workload, False)
    config, traffic = loaded["config"], loaded["traffic"]
    run.device_info(int(loaded["cell"]["chips"]))
    run.enable_cache()
    svc = run.Service(config, {})
    out = []
    try:
        run.warm(svc, arrivals.warmup(config, traffic, seed))
        for i, rate in enumerate(rates):
            mix = dict(traffic, rate_hz=float(rate))
            plan = arrivals.open_plan(config, mix, seed + i, seconds)
            win = run.Window(svc, seconds)
            win.open(plan)
            win.join()
            recs = win.records
            lat = [(r["done"] - r["due"]) for r in recs if "done" in r]
            third = [[r["done"] - r["due"] for r in recs
                      if "done" in r and lo <= r["due"] < hi]
                     for lo, hi in ((0, seconds / 3),
                                    (2 * seconds / 3, seconds))]
            first, last = (statistics.median(t) if t else None
                           for t in third)
            row = {"rate_hz": rate, "due": len(recs),
                   "answered": len(lat),
                   "answered_in_window": sum(1 for r in recs
                                             if r.get("done", 1e9)
                                             <= seconds),
                   "latency_p50_s": statistics.median(lat) if lat else None,
                   "first_third_p50_s": first, "last_third_p50_s": last,
                   "sustained": bool(lat) and len(lat) == len(recs)
                   and last is not None and first is not None
                   and last <= 2 * first + 0.25}
            print(json.dumps(row), flush=True)
            out.append(row)
            if not row["sustained"]:
                break           # the knee lies below; higher rates only queue
            svc.wait_idle(120)
    finally:
        svc.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from bench import run
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    try:
        sweep(args.workload, [float(r) for r in args.rates.split(",")],
              args.seconds, args.seed)
    except run.NoChip as e:
        print(f"bench.knee: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
