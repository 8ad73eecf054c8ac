"""Plain reference for exact treewidth: the Held-Karp dynamic program over
vertex subsets (Bodlaender, Fomin, Koster, Kratsch, Thilikos, "On exact
algorithms for treewidth", 2012), written with numpy and nothing else.

tw(G) <= k iff the vertices can be eliminated one by one so that each
vertex v, eliminated after the set S, has |Q(S, v)| <= k, where Q(S, v)
is the set of vertices outside S + {v} that v reaches through S.  The
program below keeps, level by level, every set S that can be eliminated
so (a breadth-first walk of the subset lattice, deduplicated with
``np.unique``), as the paper's GPU algorithm does.  One standard
reduction is used: for any clique C there is an optimal elimination
order that ends with C (Bodlaender and Koster), so C is never eliminated
and the walk stops once at most max(k + 1, |C|) vertices remain.

Graphs are given as (n, edges) and held as one uint64 bitmask per
vertex, so n <= 64.  Nothing here imports the system under test.
"""
from __future__ import annotations

import numpy as np

U64 = np.uint64
ONE = np.uint64(1)
MAX_N = 64
# states whose candidate pairs are expanded in one numpy pass
_CHUNK = 1 << 15


def adjacency(n: int, edges) -> np.ndarray:
    """One uint64 neighbour mask per vertex; rejects self-loops and
    vertices out of range."""
    if not 0 <= n <= MAX_N:
        raise ValueError(f"n={n} outside 0..{MAX_N}")
    adj = np.zeros(n, dtype=U64)
    for u, v in edges:
        u, v = int(u), int(v)
        if u == v or not (0 <= u < n and 0 <= v < n):
            raise ValueError(f"bad edge ({u}, {v}) for n={n}")
        adj[u] |= ONE << U64(v)
        adj[v] |= ONE << U64(u)
    return adj


def _tables(adj: np.ndarray) -> np.ndarray:
    """tab[b, x] = union of the neighbourhoods of the vertices whose bits
    are set in byte b of a mask equal to x there."""
    n = len(adj)
    nbytes = max(1, (n + 7) // 8)
    tab = np.zeros((nbytes, 256), dtype=U64)
    for b in range(nbytes):
        for x in range(1, 256):
            low = (x & -x).bit_length() - 1
            v = 8 * b + low
            tab[b, x] = tab[b, x & (x - 1)] | (adj[v] if v < n else U64(0))
    return tab


def _neighbours(masks: np.ndarray, tab: np.ndarray) -> np.ndarray:
    out = np.zeros_like(masks)
    for b in range(tab.shape[0]):
        out |= tab[b][((masks >> U64(8 * b)) & U64(255)).astype(np.intp)]
    return out


def greedy_clique(adj: np.ndarray) -> int:
    """A clique (as a mask), grown greedily from the highest degrees."""
    n = len(adj)
    deg = [int(np.bitwise_count(a)) for a in adj]
    clique, cand = 0, (1 << n) - 1
    for v in sorted(range(n), key=lambda v: (-deg[v], v)):
        if cand >> v & 1:
            clique |= 1 << v
            cand &= int(adj[v])
    return clique


def degeneracy(adj: np.ndarray) -> int:
    """Largest minimum degree met while deleting a minimum-degree vertex
    at a time: a lower bound on treewidth."""
    n = len(adj)
    alive = (1 << n) - 1
    best = 0
    nb = [int(a) for a in adj]
    for _ in range(n):
        v = min((u for u in range(n) if alive >> u & 1),
                key=lambda u: (bin(nb[u] & alive).count("1"), u))
        best = max(best, bin(nb[v] & alive).count("1"))
        alive &= ~(1 << v)
    return best


def decide(adj: np.ndarray, k: int, clique: int = 0) -> bool:
    """Is tw <= k?  ``clique`` is a clique mask kept to the end."""
    n = len(adj)
    csize = bin(clique).count("1")
    if csize > k + 1:
        return False
    levels = n - max(k + 1, csize)
    if levels <= 0:
        return True
    tab = _tables(adj)
    allowed = [v for v in range(n) if not clique >> v & 1]
    frontier = np.zeros(1, dtype=U64)
    for _ in range(levels):
        children = []
        for lo in range(0, len(frontier), _CHUNK):
            s_all = frontier[lo:lo + _CHUNK]
            for v in allowed:
                vb = ONE << U64(v)
                s = s_all[(s_all & vb) == 0]
                if not len(s):
                    continue
                t = s | vb
                reach = np.full(len(s), vb, dtype=U64)
                while True:
                    grown = reach | (_neighbours(reach, tab) & t)
                    if np.array_equal(grown, reach):
                        break
                    reach = grown
                q = _neighbours(reach, tab) & ~t
                ok = np.bitwise_count(q) <= k
                if ok.any():
                    children.append(t[ok])
        if not children:
            return False
        frontier = np.unique(np.concatenate(children))
    return True


def treewidth(n: int, edges) -> int:
    """Exact treewidth: decide k = lower bound, lower bound + 1, ... and
    return the first k that holds."""
    if n <= 1:
        return 0
    adj = adjacency(n, edges)
    if not adj.any():
        return 0
    clique = greedy_clique(adj)
    k = max(degeneracy(adj), bin(clique).count("1") - 1, 1)
    while not decide(adj, k, clique):
        k += 1
    return k
