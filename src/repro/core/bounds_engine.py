"""Anytime heuristic bounds engine (Tamaki-style improvers).

The exact ladder only moves a request's bounds when a Held-Karp rung
decides; on a heavy graph the client stares at admission-time bounds for
the whole climb.  This module supplies the cheap anytime improvers of
Tamaki's "Heuristic computation of exact treewidth" wired around the
paper's $O^*(2^n)$ DP:

  * upper bounds   -- min-degree / min-fill / seeded randomized
    elimination sweeps.  The randomized min-degree sweep also compiles to
    a single vmapped JAX kernel (`ub_orders_async`) so every admitted
    request in the pool shares one dispatch per improver round.
  * lower bounds   -- degeneracy and MMW over randomized edge
    contractions (`contraction_lb`): each step contracts a min-degree
    vertex into a random neighbour; every intermediate graph is a minor,
    so its min degree bounds tw from below.

Improvers only ever *tighten* (ub via a replayable elimination-order
certificate, lb via a minor argument), so consumers may clamp the exact
ladder with them without changing any verdict: rungs below an improved
lb are already refuted, rungs at or above an improved ub are already
certified.  `HeuristicState` packages the bounds-only serving mode
(`heuristic_only=True`) behind the same duck-typed surface the scheduler
uses for exact instances.

Everything is deterministic per (graph, seed): seeds thread explicitly,
never from global RNG state.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, Optional, Sequence

import numpy as np

from . import bounds, telemetry
from .graph import Graph
from .solver import SolveResult

_MIX = 1000003  # seed mixer: keeps per-round streams disjoint


def _round_seed(seed: int, rnd: int) -> int:
    return (int(seed) * _MIX + int(rnd)) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# lower-bound improver: MMW on randomized edge contractions (host, numpy)
# ---------------------------------------------------------------------------

def contraction_lb(g: Graph, seed: int = 0) -> int:
    """One seeded MMW contraction sweep; returns a valid lower bound.

    Repeatedly record the current minimum degree (each contracted graph
    is a minor of ``g``, and tw >= degeneracy >= min degree of any
    minor), then contract a minimum-degree vertex into a uniformly
    random neighbour.  Randomizing the partner explores contraction
    sequences the deterministic tiebreak of `mmw.mmw_oracle` never
    visits, so distinct seeds can tighten past the admission-time MMW.
    """
    n = g.n
    if n <= 1:
        return 0
    rng = np.random.RandomState(seed)
    a = g.adj.copy()
    alive = np.ones(n, dtype=bool)
    lb = 0
    while int(alive.sum()) > 1:
        cand = np.nonzero(alive)[0]
        deg = a[cand].sum(axis=1)
        lb = max(lb, int(deg.min()))
        v = int(cand[int(np.argmin(deg))])
        nbrs = np.nonzero(a[v])[0]
        if len(nbrs) == 0:
            alive[v] = False
            continue
        u = int(nbrs[rng.randint(len(nbrs))])
        merged = a[u] | a[v]
        merged[u] = merged[v] = False
        a[u] = merged
        a[:, u] = merged
        a[v] = False
        a[:, v] = False
        alive[v] = False
    return lb


# ---------------------------------------------------------------------------
# host improvement loop (solver path): rounds of ub sweeps + lb contractions
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Improvement:
    """Result of a host improvement run; bounds only ever tighten."""
    lb: int
    ub: int
    ub_order: Optional[list]
    lb_moves: int = 0
    ub_moves: int = 0

    @property
    def closed(self) -> bool:
        return self.lb >= self.ub


_UB_STRATEGIES = ("min_degree", "min_fill")


def improve(g: Graph, lb: int = 0, ub: Optional[int] = None,
            ub_order: Optional[list] = None, *, rounds: int = 1,
            seed: int = 0, tracker=None) -> Improvement:
    """Run ``rounds`` improver rounds on the host; monotone by clamping.

    Each round draws one seeded randomized elimination sweep (strategy
    rotating min-degree / min-fill) and one seeded MMW contraction
    sweep.  Pure function of (g, lb, ub, rounds, seed) — the solver and
    the batched scheduler admission agree bit-for-bit.
    """
    tr = telemetry.get(tracker)
    if ub is None:
        ub = max(0, g.n - 1)
    out = Improvement(lb, ub, list(ub_order) if ub_order is not None else None)
    if g.n <= 1:
        return out
    for r in range(max(0, rounds)):
        if out.closed:
            break
        s = _round_seed(seed, r)
        strat = _UB_STRATEGIES[r % len(_UB_STRATEGIES)]
        w, o = bounds.randomized_order(g, s, strat)
        if w < out.ub:
            out.ub, out.ub_order = w, o
            out.ub_moves += 1
            tr.count(heur_ub_improvements=1)
        l = contraction_lb(g, s)
        if l > out.lb:
            out.lb = l
            out.lb_moves += 1
            tr.count(heur_lb_improvements=1)
    return out


# size gates: min-fill and the python MMW oracle are O(n^3)-ish host
# loops — fine for exact-tier graphs, too slow at heuristic-only scale
_EXPENSIVE_N = 64


def quick_bounds(g: Graph, seed: int = 0) -> tuple:
    """Admission-time (lb, ub, ub_order) sized to the graph.

    Below `_EXPENSIVE_N` this matches the exact planner's bounds
    (degeneracy + MMW + clique, min-degree + min-fill); above it the
    cubic sweeps are dropped so admission stays cheap on graphs beyond
    exact-DP reach.
    """
    n = g.n
    if n <= 1:
        return 0, 0, list(range(n))
    if n <= _EXPENSIVE_N:
        lb = bounds.lower_bound(g, seed=seed)
        ub, order = bounds.upper_bound(g, seed=seed)
    else:
        lb = max(bounds.degeneracy(g),
                 len(bounds.greedy_max_clique(g, tries=8, seed=seed)) - 1)
        ub, order = bounds._elimination_ub(g, "min_degree")
    return lb, min(ub, n - 1), order


# ---------------------------------------------------------------------------
# batched ub improver: one vmapped dispatch covers the whole pool
# ---------------------------------------------------------------------------

def _kernel(n: int):
    """Jitted randomized min-degree elimination over (B, n, n) bool adj."""
    import jax
    import jax.numpy as jnp

    eye = np.eye(n, dtype=bool)

    def one(adj, rank):
        def body(i, carry):
            adj, alive, width, order = carry
            deg = adj.sum(axis=1).astype(jnp.int32)
            score = jnp.where(alive, deg * (n + 1) + rank, jnp.int32(2 ** 30))
            v = jnp.argmin(score).astype(jnp.int32)
            width = jnp.maximum(width, deg[v])
            nb = adj[v]
            adj = adj | (nb[:, None] & nb[None, :])
            keep = ~(jnp.arange(n, dtype=jnp.int32) == v)
            adj = adj & keep[:, None] & keep[None, :] & ~eye
            alive = alive & keep
            order = order.at[i].set(v)
            return adj, alive, width, order

        carry = (adj, jnp.ones((n,), dtype=bool), jnp.int32(0),
                 jnp.zeros((n,), dtype=jnp.int32))
        _, _, width, order = jax.lax.fori_loop(0, n, body, carry)
        return width, order

    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=None)
def _kernel_cached(n: int):
    return _kernel(n)


def ub_orders_async(graphs: Sequence[Graph], seeds: Sequence[int], *,
                    tracker=None) -> Any:
    """Launch ONE vmapped randomized min-degree sweep over the pool.

    Pads every lane to a shared n (isolated pad vertices eliminate first
    at degree 0 and cannot raise any width), launches the jitted kernel,
    and returns an `engine.DispatchHandle` whose ``result()`` yields one
    ``(width, order)`` per input graph — the order filtered back to the
    graph's real vertices, the width exactly what a host replay of that
    order produces.  Seeds pick the per-lane random tiebreak rank, so
    each lane is deterministic per (graph, seed).
    """
    from . import engine  # deferred: engine pulls in the backend registry
    import jax.numpy as jnp

    tr = telemetry.get(tracker)
    if not graphs:
        return engine.DispatchHandle((), lambda host: [], _result=[],
                                     _done=True)
    n_max = max(g.n for g in graphs)
    n_pad = max(16, -(-n_max // 16) * 16)      # round up: stable jit shapes
    b = len(graphs)
    adjs = np.zeros((b, n_pad, n_pad), dtype=bool)
    ranks = np.zeros((b, n_pad), dtype=np.int32)
    for i, (g, s) in enumerate(zip(graphs, seeds)):
        adjs[i, :g.n, :g.n] = g.adj
        ranks[i] = np.random.RandomState(int(s) % (2 ** 31 - 1)) \
            .permutation(n_pad).astype(np.int32)
    widths, orders = _kernel_cached(n_pad)(jnp.asarray(adjs),
                                           jnp.asarray(ranks))
    tr.count(heur_dispatches=1, heur_lanes=b)
    ns = [g.n for g in graphs]

    def finalize(host):
        ws, os_ = host
        out = []
        for i, n in enumerate(ns):
            order = [int(v) for v in os_[i] if int(v) < n]
            out.append((int(ws[i]), order))
        return out

    return engine.DispatchHandle((widths, orders), finalize, tracker=tr)


# ---------------------------------------------------------------------------
# heuristic-only serving state (duck-types the scheduler's InstanceState)
# ---------------------------------------------------------------------------

class HeuristicState:
    """Bounds-only request state: no exact rungs, just improver rounds.

    Mirrors the slice of `ladder.InstanceState` the scheduler touches
    (``run``/``result``/``bounds``/``partial``/``anytime_result``/
    ``improve_bounds``), with ``run`` pinned to None so the launch loop
    never packs DP rungs for it.  Terminates when lb meets ub (then the
    verdict is *exact* — both sides are certificates) or when the
    improver round budget is spent, with ``exact=(lb == ub)``.
    """

    run = None  # never holds a DP ladder

    def __init__(self, g: Graph, *, seed: int = 0,
                 max_rounds: int = 16, tracker=None):
        self.g = g
        self.seed = int(seed)
        self.max_rounds = max(1, int(max_rounds))
        self.rounds_done = 0
        self.tracker = telemetry.get(tracker)
        self.t0 = time.time()
        self.result = None
        with self.tracker.time_block("heur_admit_s"):
            lb, ub, order = quick_bounds(g, seed=self.seed)
        self.lb, self.ub, self.ub_order = lb, ub, order
        if self.lb >= self.ub:
            self._finalize()

    def bounds(self) -> tuple:
        return self.lb, self.ub

    def partial(self) -> tuple:
        return 0, {}

    def max_n(self) -> int:
        return self.g.n

    def anytime_result(self, lb=None, ub=None):
        lb = self.lb if lb is None else max(lb, self.lb)
        ub = self.ub if ub is None else min(ub, self.ub)
        return SolveResult(ub, lb == ub, lb, ub, 0, time.time() - self.t0,
                           order=self.ub_order, per_k={})

    def improve_bounds(self, lb=None, ub=None, ub_order=None) -> dict:
        """Clamp in an improver result; monotone tighten only."""
        out = dict(lb_improved=False, ub_improved=False, rungs_skipped=0,
                   finished=False)
        if self.result is not None:
            return out
        if ub is not None and ub < self.ub and ub_order is not None:
            self.ub, self.ub_order = int(ub), list(ub_order)
            out["ub_improved"] = True
        if lb is not None and lb > self.lb:
            self.lb = min(int(lb), self.ub)
            out["lb_improved"] = True
        if self.lb >= self.ub:
            self._finalize()
            out["finished"] = True
        return out

    def step_done(self) -> bool:
        """Account one finished improver round; True once terminal."""
        self.rounds_done += 1
        if self.result is None and self.rounds_done >= self.max_rounds:
            self._finalize()
        return self.result is not None

    def _finalize(self):
        self.result = SolveResult(
            self.ub, self.lb == self.ub, self.lb, self.ub, 0,
            time.time() - self.t0, order=self.ub_order, per_k={})
