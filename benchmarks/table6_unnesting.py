"""Table 6 analogue: loop-scheduling sweep.

The paper's "loop unnesting" flattens three nested loops into a state
machine to trade branch divergence against bookkeeping.  The TPU analogue
is the component-closure fixpoint schedule:
  doubling : static ceil(log2 n) trip count   — (inf,inf): no divergence,
             some wasted converged iterations;
  while    : data-dependent early exit        — (1,1): minimal work, but a
             batched while runs until the LAST lane converges (the SIMD
             form of waiting on the slowest thread);
  linear   : one-hop per iteration            — the paper's per-level BFS.
The paper found the unmodified nested loop fastest; doubling is our
analogous default and the sweep verifies the same ordering holds.
"""
from __future__ import annotations

from repro.core import ladder

from .common import Timer, emit, get_instance

INSTANCES = ["queen5_5", "queen6_6", "petersen", "myciel3"]
SCHEDULES = ["doubling", "while", "linear"]


def run():
    for key in INSTANCES:
        g = get_instance(key)
        widths = set()
        for sched in SCHEDULES:
            with Timer() as t:
                r = ladder.solve(g, cap=1 << 16, block=1 << 9,
                                 schedule=sched)
            widths.add(r.width)
            emit(f"table6/{key}/{sched}", t.seconds,
                 f"tw={r.width};exp={r.expanded}")
        assert len(widths) == 1


if __name__ == "__main__":
    run()
