"""The paths rule (Clautiaux et al., the paper's rule 2) on the ladder.

``ladder.plan_block`` computes disjoint-path counts only for pairs that
can add an edge to some ``G_k`` with ``k0 <= k < ub`` (non-adjacent, both
degrees at least ``k0 + 1``), and stops each max-flow at ``ub``.  These
tests pin every ``G_k`` the ladder can read to the one built from the
plain all-pairs max-flow, pin the counters that say how many flows ran,
and check that ``graph_at`` refuses rungs off the ladder while served
solves still reach the golden widths.
"""
import functools
import json
import os

import numpy as np
import pytest

from repro.core import bounds, graph, ladder, telemetry
from repro.serve.twscheduler import TwScheduler

GOLDEN = json.load(open(os.path.join(os.path.dirname(__file__),
                                     "golden_widths.json")))

TABLE1 = {
    "petersen": graph.petersen,
    "myciel3": lambda: graph.myciel(3),
    "myciel4": lambda: graph.myciel(4),
    "desargues": graph.desargues,
    "mcgee": graph.mcgee,
    "queen5_5": lambda: graph.queen(5),
    "queen6_6": lambda: graph.queen(6),
}
# seeded G(n, p) graphs whose plans run a ladder (lb < ub)
GNP = {f"gnp{n}_{p}_{s}": functools.partial(graph.gnp, n, p, s)
       for n, p, s in [(9, 0.4, 2), (10, 0.6, 4), (12, 0.5, 2),
                       (16, 0.3, 1), (16, 0.5, 1), (16, 0.5, 2),
                       (20, 0.3, 1), (20, 0.3, 2), (20, 0.5, 2)]}
GRAPHS = {**TABLE1, **GNP}


def _all_pairs_paths(g: graph.Graph, cap: int) -> np.ndarray:
    """Reference: the internally-disjoint path count of every pair, each
    by BFS augmentation on a fresh node-split network (v_in = 2v, v_out =
    2v + 1), run to ``cap + 1`` flows."""
    n = g.n
    out = np.zeros((n, n), dtype=np.int32)
    for s in range(n):
        for t in range(s + 1, n):
            res = {}
            for v in range(n):
                res[(2 * v, 2 * v + 1)] = 1 if v not in (s, t) else cap + 1
            for u, v in np.argwhere(g.adj):
                res[(2 * u + 1, 2 * v)] = 1
            for a, b in list(res):
                res.setdefault((b, a), 0)
            nxt = [[] for _ in range(2 * n)]
            for a, b in res:
                nxt[a].append(b)
            src, snk = 2 * s + 1, 2 * t
            flow = 0
            while flow <= cap:
                parent = {src: None}
                q = [src]
                while q and snk not in parent:
                    nq = []
                    for a in q:
                        for b in nxt[a]:
                            if b not in parent and res[(a, b)] > 0:
                                parent[b] = a
                                nq.append(b)
                    q = nq
                if snk not in parent:
                    break
                b = snk
                while parent[b] is not None:
                    a = parent[b]
                    res[(a, b)] -= 1
                    res[(b, a)] += 1
                    b = a
                flow += 1
            out[s, t] = out[t, s] = flow
    return out


@functools.lru_cache(maxsize=None)
def _reference(name: str, cap: int) -> np.ndarray:
    return _all_pairs_paths(GRAPHS[name](), cap)


def _plan(g, start_k=None):
    return ladder.plan_block(g, use_clique=True, use_paths=True,
                             start_k=start_k)


def _cases():
    out = []
    for name in GRAPHS:
        out.append((name, None))
        out.append((name, "below_lb"))
    out += [("petersen", "zero"), ("queen5_5", "zero"),
            ("myciel4", "above_lb"), ("gnp16_0.5_2", "zero")]
    return out


def _start_k(name, how):
    if how is None:
        return None
    lb = _plan(GRAPHS[name]()).lb
    return {"zero": 0, "below_lb": max(0, lb - 2), "above_lb": lb + 1}[how]


@pytest.mark.parametrize("name,how", _cases())
def test_graph_at_matches_all_pairs_rule(name, how):
    """Every rung's G_k equals the G_k of the unpruned all-pairs counts,
    including ladders whose start is forced below (or above) lb."""
    g = GRAPHS[name]()
    plan = _plan(g, _start_k(name, how))
    assert plan.result is None and plan.paths is not None
    if how in ("zero", "below_lb"):
        assert plan.k0 < plan.lb or plan.lb == 0
    ref = _reference(name, plan.ub)
    for k in range(plan.k0, plan.ub):
        want = g.with_edges(bounds.paths_edges(g, ref, k))
        assert (plan.graph_at(k).adj == want.adj).all(), (name, how, k)


def test_pruned_counts_are_capped_exact_counts():
    """Where the pruned matrix runs a flow, it reads the exact count
    clamped to ``cap``; every skipped pair reads 0."""
    g = graph.myciel(4)
    ref = _reference("myciel4", 11)
    for k_min in (0, 3, 8):
        got = bounds.disjoint_paths_matrix(g, cap=11, k_min=k_min)
        ran = bounds.paths_candidates(g, k_min)
        ran = ran | ran.T
        assert (got[ran] == np.minimum(ref[ran], 11)).all()
        assert (got[~ran] == 0).all()
        assert (got == got.T).all()


@pytest.mark.parametrize("name,flows", [("petersen", "none"),
                                        ("mcgee", "none"),
                                        ("queen5_5", "some")])
def test_plan_counts_the_flows_it_ran(name, flows):
    g = GRAPHS[name]()
    plan = _plan(g)
    assert plan.paths_pairs == g.n * (g.n - 1) // 2
    if flows == "none":
        assert plan.paths_flows == 0
    else:
        assert 0 < plan.paths_flows < plan.paths_pairs
    tr = telemetry.root().child(f"paths_{name}")
    ladder.InstanceState(g, use_preprocess=True,
                         plan_kw=dict(use_clique=True, use_paths=True,
                                      start_k=None), tracker=tr)
    assert tr.value("paths_flows") == plan.paths_flows
    assert tr.value("paths_pairs") == plan.paths_pairs


@pytest.mark.parametrize("name", ["petersen", "queen5_5"])
def test_graph_at_refuses_rungs_off_the_ladder(name):
    plan = _plan(GRAPHS[name]())
    plan.graph_at(plan.k0)
    plan.graph_at(plan.ub - 1)
    for k in (plan.k0 - 1, plan.ub, plan.ub + 1):
        with pytest.raises(ValueError):
            plan.graph_at(k)


def test_served_solves_keep_golden_widths_and_roll_up_counters():
    """queen5_5 and myciel4 through a pipelined, speculating scheduler
    with the paths rule on: golden widths, exact, and the paths counters
    of both admissions summed into the pool's totals."""
    gs = [graph.queen(5), graph.myciel(4)]
    sched = TwScheduler(lanes=2, block=1 << 10, use_paths=True, pipeline=2)
    rids = [sched.submit(g, speculate=2) for g in gs]
    done = sched.run()
    for g, rid in zip(gs, rids):
        assert done[rid].exact and done[rid].width == GOLDEN[g.name]["tw"]
    m = sched.metrics()
    pool = m["pool"]["counters"]
    reqs = [m["requests"][rid]["counters"] for rid in rids]
    for key in ("paths_flows", "paths_pairs"):
        assert pool[key] == sum(r[key] for r in reqs)
    assert 0 < pool["paths_flows"] < pool["paths_pairs"]
