"""Table 4/5 analogue: minor-min-width pruning on/off.

The paper found MMW prunes few states (graphs explored have weak MMW
bounds) while costing 2-3x runtime; this benchmark reproduces that
trade-off measurement on the generatable suite."""
from __future__ import annotations

from repro.core import ladder

from .common import Timer, emit, get_instance

INSTANCES = ["petersen", "myciel3", "queen5_5", "queen6_6", "desargues"]


def run():
    for key in INSTANCES:
        g = get_instance(key)
        res = {}
        for mmw in (False, True):
            with Timer() as t:
                r = ladder.solve(g, cap=1 << 16, block=1 << 9, use_mmw=mmw)
            res[mmw] = (r, t.seconds)
            emit(f"table4/{key}/{'mmw' if mmw else 'none'}", t.seconds,
                 f"tw={r.width};exp={r.expanded}")
        r0, t0 = res[False]
        r1, t1 = res[True]
        assert r0.width == r1.width
        assert r1.expanded <= r0.expanded       # MMW can only prune
        emit(f"table4/{key}/summary", t1,
             f"prune_ratio={r1.expanded / max(r0.expanded, 1):.3f};"
             f"slowdown={t1 / max(t0, 1e-9):.2f}")


if __name__ == "__main__":
    run()
