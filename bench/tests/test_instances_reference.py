"""The benchmark's instance generators and its plain reference, on CPU.

Run from the checkout root:  python -m pytest bench/tests
"""
import json
import os

import numpy as np
import pytest

from bench import reference
from bench.instances import table1

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GOLDEN = {k: v for k, v in json.load(open(os.path.join(
    ROOT, "tests", "golden_widths.json"))).items() if not k.startswith("_")}
# Table-1 instances the reference solves in seconds (dyck takes minutes)
QUICK = ["petersen", "myciel3", "myciel4", "queen5_5", "desargues",
         "mcgee", "queen6_6"]


@pytest.mark.parametrize("name", QUICK)
def test_reference_matches_golden_width(name):
    n, edges = table1.GENERATORS[name]()
    assert reference.treewidth(n, edges) == GOLDEN[name]["tw"]


@pytest.mark.parametrize("name", sorted(table1.GENERATORS))
def test_published_width_is_the_golden_width(name):
    assert table1.PUBLISHED[name] == GOLDEN[name]["tw"]


@pytest.mark.parametrize("name", sorted(table1.GENERATORS))
def test_generators_match_the_programs_graphs(name):
    """Same graphs as the program's registry, vertex for vertex (the
    benchmark keeps its own copy so that no later change moves it)."""
    from repro.core import graph
    g = graph.REGISTRY[name]()
    n, edges = table1.GENERATORS[name]()
    assert n == g.n
    ours = {tuple(sorted(e)) for e in edges}
    theirs = {(u, v) for u in range(g.n) for v in range(u + 1, g.n)
              if g.adj[u][v]}
    assert ours == theirs


def held_karp(n, edges):
    """Treewidth by the unpruned subset recursion TW(S) = min over v in S
    of max(TW(S - v), |Q(S - v, v)|), for tiny graphs."""
    adj = [0] * n
    for u, v in edges:
        adj[u] |= 1 << v
        adj[v] |= 1 << u

    def q(s, v):
        reach, frontier = 1 << v, 1 << v
        while frontier:
            nxt = 0
            for u in range(n):
                if frontier >> u & 1:
                    nxt |= adj[u]
            nxt &= (s | 1 << v) & ~reach
            reach |= nxt
            frontier = nxt
        out = 0
        for u in range(n):
            if reach >> u & 1:
                out |= adj[u]
        return bin(out & ~(s | 1 << v)).count("1")

    tw = {0: -1}
    for s in range(1, 1 << n):
        tw[s] = min(max(tw[s & ~(1 << v)], q(s & ~(1 << v), v))
                    for v in range(n) if s >> v & 1)
    return tw[(1 << n) - 1]


@pytest.mark.parametrize("seed", range(40))
def test_reference_matches_unpruned_recursion(seed):
    """On random G(n, p) graphs: edge u-v present with probability p."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 10))
    a = np.triu(rng.random((n, n)) < float(rng.choice([0.2, 0.35, 0.6])), 1)
    edges = [(int(u), int(v)) for u, v in zip(*np.nonzero(a))]
    assert reference.treewidth(n, edges) == max(0, held_karp(n, edges))


def test_relabelling_keeps_the_graph():
    rng = np.random.default_rng(3)
    [req] = table1.build({"names": ["queen5_5"]}, rng, 1)
    assert reference.treewidth(req["n"], req["edges"]) == 18
    n, edges = table1.GENERATORS["queen5_5"]()
    assert sorted(req["edges"]) != sorted(map(tuple, edges))


def test_build_gives_every_seed_the_same_instances():
    params = {"names": sorted(table1.GENERATORS)}
    a = table1.build(params, np.random.default_rng(1), 16)
    b = table1.build(params, np.random.default_rng(2), 16)
    for lo in (0, 8):
        assert (sorted(r["key"] for r in a[lo:lo + 8])
                == sorted(r["key"] for r in b[lo:lo + 8]) == params["names"])
    assert [r["key"] for r in a] != [r["key"] for r in b]
