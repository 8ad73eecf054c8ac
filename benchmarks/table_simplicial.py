"""Beyond-paper: simplicial-vertex pruning (the rule the paper's §5 poses
as future work).  States explored with/without branch collapsing."""
from __future__ import annotations

from repro.core import ladder

from .common import Timer, emit, get_instance

INSTANCES = ["petersen", "myciel3", "queen5_5", "desargues"]


def run():
    for key in INSTANCES:
        g = get_instance(key)
        res = {}
        for simp in (False, True):
            with Timer() as t:
                r = ladder.solve(g, cap=1 << 16, block=1 << 9,
                                 use_simplicial=simp)
            res[simp] = (r, t.seconds)
            emit(f"simplicial/{key}/{'on' if simp else 'off'}", t.seconds,
                 f"tw={r.width};exp={r.expanded}")
        r0, _ = res[False]
        r1, _ = res[True]
        assert r0.width == r1.width
        assert r1.expanded <= r0.expanded


if __name__ == "__main__":
    run()
