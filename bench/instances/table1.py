"""The paper's Table-1 instances that can be generated rather than read:
DIMACS queen graphs and Mycielski graphs, and the named cubic graphs
(Petersen, Desargues, McGee, Dyck).

Each request carries the instance's published treewidth (``PUBLISHED``)
as ``width``: the answer the run is checked against.  Each of those
widths but dyck's is confirmed by ``bench/reference.py`` in
``bench/tests`` (dyck's, 7, by the repository's own Held-Karp oracle,
``tests/golden_widths.json``; the plain reference takes over 13 minutes
on it).

``build(params, rng, count)`` returns ``count`` requests.  They walk the
named list in rounds; each round is the whole list in an order drawn from
``rng``, so every seed sends the same instances in another order.  Each
request is relabelled by its own random permutation, so the service sees
a new labelling every time (the result cache, where a pool has one, can
only hit through its canonical form).
"""
from __future__ import annotations

import itertools


def queen(k: int):
    """k x k queen graph: squares, joined by one queen move."""
    cells = list(itertools.product(range(k), repeat=2))
    edges = [(i, j) for i, (a, b) in enumerate(cells)
             for j, (c, d) in enumerate(cells)
             if i < j and (a == c or b == d or abs(a - c) == abs(b - d))]
    return k * k, edges


def myciel(k: int):
    """DIMACS myciel-k: Mycielski's construction applied k - 1 times to
    one edge (myciel3 = Groetzsch graph, 11 vertices)."""
    n, edges = 2, [(0, 1)]
    for _ in range(k - 1):
        new = []
        for u, v in edges:
            new += [(u, v), (u, n + v), (v, n + u)]
        new += [(n + u, 2 * n) for u in range(n)]
        n, edges = 2 * n + 1, new
    return n, edges


def lcf(n: int, jumps):
    """Cubic Hamiltonian graph in LCF notation: the cycle 0..n-1 plus a
    chord from i to i + jumps[i mod len(jumps)]."""
    edges = {tuple(sorted((i, (i + 1) % n))) for i in range(n)}
    edges |= {tuple(sorted((i, (i + jumps[i % len(jumps)]) % n)))
              for i in range(n)}
    return n, sorted(edges)


def petersen():
    """Kneser graph K(5, 2): 2-subsets of 5, joined when disjoint."""
    subs = list(itertools.combinations(range(5), 2))
    return 10, [(i, j) for i in range(10) for j in range(i + 1, 10)
                if not set(subs[i]) & set(subs[j])]


GENERATORS = {
    "petersen": petersen,
    "desargues": lambda: lcf(20, [5, -5, 9, -9]),
    "mcgee": lambda: lcf(24, [12, 7, -7]),
    "dyck": lambda: lcf(32, [5, -5, 13, -13]),
    "myciel3": lambda: myciel(3),
    "myciel4": lambda: myciel(4),
    "queen5_5": lambda: queen(5),
    "queen6_6": lambda: queen(6),
}


# the treewidths of these graphs, as tests/golden_widths.json has them
PUBLISHED = {"petersen": 4, "myciel3": 5, "myciel4": 10, "queen5_5": 18,
             "queen6_6": 25, "desargues": 6, "mcgee": 7, "dyck": 7}


def relabel(n: int, edges, rng):
    perm = rng.permutation(n)
    return sorted(tuple(sorted((int(perm[u]), int(perm[v]))))
                  for u, v in edges)


def build(params: dict, rng, count: int) -> list:
    """``params["names"]``: the instances of the mix, by name."""
    names = list(params["names"])
    unknown = sorted(set(names) - set(GENERATORS))
    if unknown:
        raise ValueError(f"unknown Table-1 instances {unknown}")
    base = {name: GENERATORS[name]() for name in names}
    out = []
    while len(out) < count:
        for name in rng.permutation(names):
            n, edges = base[str(name)]
            out.append({"key": str(name), "ref_key": str(name), "n": n,
                        "edges": relabel(n, edges, rng),
                        "width": PUBLISHED[str(name)]})
    return out[:count]


def warmup(params: dict, rng) -> list:
    """One request of each instance: together they cover every program
    shape the mix can reach."""
    return build(params, rng, len(params["names"]))
