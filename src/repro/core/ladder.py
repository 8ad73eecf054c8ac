"""The deepening ladder: one loop decides which rungs to run, and in what
order, for every solve path.

Structure mirrors the paper (Listing 1 + §3.1 optimizations):

  for k = lb .. ub-1:                      (iterative deepening)
      G_k = G + edges{pairs with >= k+1 vertex-disjoint paths}   [rule 2]
      decide tw(G_k) <= k                  (one rung, see ``solver``)
      k feasible -> tw = k

over the biconnected blocks of the preprocessed graph.  This module owns
everything above one rung — the rung order, speculation, exactness, the
per-rung accounting and the fold over preprocess blocks — and nothing
below it:

  * ``plan_block`` / ``BlockPlan`` — bounds and the rung schedule of one
    block (clique, lb/ub, the paths rule's ``G_k``, a forced start);
  * ``SuiteFold`` / ``stitch_and_verify`` — the fold of block results
    into one instance result and its certified elimination order;
  * ``InstanceState`` — one instance advanced rung by rung as verdicts
    are fed back; the serve scheduler (``repro.serve.twscheduler``)
    keeps one per admitted request;
  * ``solve`` / ``solve_many`` / ``solve_distributed`` — the round loop
    over one instance, a suite, or one instance on a device mesh.

The arrows point one way: this module → {lane dispatch ``batch``,
``shard``, ``distributed``} → ``engine`` → kernels.  No module below
imports this one.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import List, Optional, Sequence

import numpy as np

from . import backend as backend_lib
from . import batch as batch_lib
from . import bitset, bloom, bounds, bounds_engine
from . import distributed as dist_lib
from . import preprocess as preprocess_lib
from . import shard as shard_lib
from . import solver
from . import telemetry
from .batch import Lane, LaneResult
from .graph import Graph
from .solver import SolveResult


# ------------------------------------------------------------- block plans

@dataclasses.dataclass
class BlockPlan:
    """Everything iterative deepening needs to run one block.

    ``result`` is set when no search is needed (trivial graph,
    ``lb >= ub``, or a forced ``start_k`` at/above ``ub``); its
    ``time_sec`` is 0 and callers stamp their own.
    """
    g: Graph
    clique: list
    lb: int
    ub: int
    ub_order: list
    paths: Optional[np.ndarray]
    k0: int              # first k of the deepening ladder
    forced: bool         # k0 was pushed above the genuine lower bound
    result: Optional[SolveResult] = None
    paths_flows: int = 0     # max-flows the paths rule ran
    paths_pairs: int = 0     # vertex pairs of the block it covered

    def graph_at(self, k: int) -> Graph:
        """G_k: the paper's rule-2 graph (improved edges for width k).

        Defined on the ladder only, ``k0 <= k < ub``: ``paths`` holds no
        threshold outside it."""
        if not self.k0 <= k < self.ub:
            raise ValueError(f"G_k for k={k} outside the ladder "
                             f"[{self.k0}, {self.ub}) of {self.g.name}")
        if self.paths is None:
            return self.g
        return self.g.with_edges(bounds.paths_edges(self.g, self.paths, k))

    def exact_at(self, k: int, any_inexact: bool) -> bool:
        """Is 'feasible at k' an exactness proof?  Only when no state was
        dropped below k AND infeasibility of k-1 was actually established
        — either k-1 < lb (genuine bound) or k-1 was decided in this run.
        A user-forced ``start_k`` above lb satisfies neither at ``k0``."""
        return (not any_inexact) and not (self.forced and k == self.k0)


def plan_block(g: Graph, *, use_clique: bool, use_paths: bool,
               start_k: Optional[int], heuristics: int = 0,
               seed: int = 0) -> BlockPlan:
    """Bounds + deepening schedule for one block.

    ``start_k`` moves the ladder's starting rung but never the *reported*
    lower bound: ``lb`` stays the genuine bound, and a start above it is
    flagged ``forced`` so a feasible verdict at that rung cannot be
    reported exact (nothing proved ``tw > start_k - 1``).

    ``heuristics > 0`` runs that many anytime improver rounds
    (``core.bounds_engine``) before scheduling the ladder: a tightened lb
    raises ``k0`` genuinely (not ``forced`` — the skipped rungs are
    refuted by a minor argument), a tightened ub shortens the ladder with
    a replayable order certificate.  ``seed`` pins every heuristic
    (clique restarts, randomized sweeps, contractions) so the plan is a
    pure function of ``(g, knobs)``; the defaults reproduce the
    heuristic-free plan bit-for-bit."""
    if g.n <= 1:
        return BlockPlan(g, [], 0, 0, list(range(g.n)), None, 0, False,
                         SolveResult(0, True, 0, 0, 0, 0.0,
                                     list(range(g.n)), {}))
    clique = bounds.greedy_max_clique(g, seed=seed) if use_clique else []
    lb = max(bounds.lower_bound(g, seed=seed), len(clique) - 1)
    ub, ub_order = bounds.upper_bound(g, seed=seed)
    if heuristics:
        imp = bounds_engine.improve(g, lb, ub, ub_order,
                                    rounds=int(heuristics), seed=seed)
        lb, ub = imp.lb, imp.ub
        ub_order = imp.ub_order if imp.ub_order is not None else ub_order
    if lb >= ub:
        return BlockPlan(g, clique, lb, ub, ub_order, None, lb, False,
                         SolveResult(ub, True, lb, ub, 0, 0.0, ub_order, {}))
    k0, forced = lb, False
    if start_k is not None:
        k0 = max(0, int(start_k))
        forced = k0 > lb
        if k0 >= ub:
            warnings.warn(
                f"start_k={start_k} >= upper bound {ub} for {g.name}: no "
                "search performed, returning the heuristic ub as an "
                "inexact result", stacklevel=3)
            return BlockPlan(g, clique, lb, ub, ub_order, None, k0, forced,
                             SolveResult(ub, False, lb, ub, 0, 0.0,
                                         ub_order, {}))
    if not use_paths:
        return BlockPlan(g, clique, lb, ub, ub_order, None, k0, forced)
    # the ladder reads G_k for k0 <= k < ub only: pairs that cannot reach
    # k0 + 1 paths are skipped, and no flow runs past ub
    return BlockPlan(
        g, clique, lb, ub, ub_order,
        bounds.disjoint_paths_matrix(g, cap=ub, k_min=k0), k0, forced,
        paths_flows=int(np.count_nonzero(bounds.paths_candidates(g, k0))),
        paths_pairs=g.n * (g.n - 1) // 2)


# ------------------------------------------------------------ block fold

@dataclasses.dataclass
class SuiteFold:
    """Accumulator folding per-block results into one instance result —
    the single source of the preprocess path's semantics."""
    width: int
    exact: bool = True
    expanded: int = 0
    lbs: int = 0
    ubs: int = 0
    per_k: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def start(cls, lb: int) -> "SuiteFold":
        return cls(width=lb, lbs=lb, ubs=lb)

    def skip(self, g: Graph) -> bool:
        """A block can't beat the width found so far (and then any
        elimination order of it fits the width budget)."""
        return g.n - 1 <= self.width

    def add(self, name: str, res: SolveResult) -> None:
        self.width = max(self.width, res.width)
        self.exact &= res.exact
        self.expanded += res.expanded
        self.lbs = max(self.lbs, res.lb)
        self.ubs = max(self.ubs, res.ub)
        self.per_k[name] = res.per_k

    def result(self, elapsed: float, order=None) -> SolveResult:
        return SolveResult(self.width, self.exact, self.lbs,
                           max(self.ubs, self.width), self.expanded,
                           elapsed, order, self.per_k)


def stitch_and_verify(g: Graph, pre, block_orders: list,
                      width: int) -> Optional[list]:
    """Stitch per-block elimination orders into a global certificate and
    replay-check it.  Returns ``None`` (with a warning) if the stitched
    order replays above the computed width."""
    order = preprocess_lib.stitch_block_orders(pre, block_orders)
    replay = solver.order_width(g, order)
    if replay > width:
        warnings.warn(
            f"stitched elimination order replays at width {replay} > "
            f"computed width {width}; dropping the order (please "
            "report — this indicates a preprocess/stitch bug)",
            stacklevel=2)
        return None
    return order


# ---------------------------------------------------------- instance state

@dataclasses.dataclass
class _Run:
    """Iterative deepening in progress on one block."""
    plan: BlockPlan
    k: int
    idx: int = 0                  # index into the preprocess block list
    expanded: int = 0
    any_inexact: bool = False
    per_k: dict = dataclasses.field(default_factory=dict)


class InstanceState:
    """One input graph's ladder state: the fold over its preprocessed
    blocks (``SuiteFold``), advanced block by block as rung verdicts are
    fed back.

    This is the per-request unit of every driver: ``solve``,
    ``solve_many`` and ``solve_distributed`` walk their instances
    through it, and the serve scheduler (``repro.serve.twscheduler``)
    keeps one per admitted request, feeding each slot's rung verdict
    after every shared dispatch.  ``result`` is set (a ``SolveResult``)
    once the instance is decided; until then ``run`` names the block
    rung currently occupying a lane.

    ``reconstruct=True`` additionally certifies the result with an
    elimination order: when a block's winning rung is found, that single
    rung is replayed once on the host loop (``keep_levels=True``) to
    snapshot its levels — the replay is *not* counted into ``expanded``,
    so the accounting is the same with or without reconstruction — and
    the block orders are stitched through the preprocess maps.
    ``recon_kw`` carries the decide kwargs for that replay (``cap=None``
    re-plans per block via ``plan_capacity``)."""

    def __init__(self, g: Graph, *, use_preprocess: bool, plan_kw: dict,
                 reconstruct: bool = False,
                 recon_kw: Optional[dict] = None, tracker=None):
        self.g = g
        self.plan_kw = plan_kw
        # per-request telemetry scope (the serve scheduler passes each
        # request's child tracker so rung/expanded counts attribute to it
        # and roll up into the pool totals); NULL here, not the root —
        # suite drivers opt in explicitly
        self.tracker = telemetry.NULL if tracker is None else tracker
        self.reconstruct = reconstruct
        self.recon_kw = dict(recon_kw or {})
        self.t0 = time.time()
        self.result: Optional[SolveResult] = None
        self.run: Optional[_Run] = None
        self.pre = None                          # preprocess.Preprocessed
        self.use_pre = use_preprocess
        self.bi = 0
        if g.n == 0:
            self.parts: list = []
            self.fold = None
            self.block_orders: list = []
            self.result = SolveResult(0, True, 0, 0, 0, 0.0, [], {})
            return
        if use_preprocess:
            self.pre = preprocess_lib.preprocess(g)
            self.parts = [b.g for b in self.pre.blocks]
            self.fold = SuiteFold.start(self.pre.lb)
        else:
            self.parts = [g]
            self.fold = None      # single block: adopt its result wholesale
        self.block_orders = [None] * len(self.parts)
        self._advance()

    def max_n(self) -> int:
        return max([p.n for p in self.parts], default=1)

    # ------------------------------------------------- anytime accounting

    def bounds(self) -> tuple:
        """Running instance-level ``(lb, ub)`` — the anytime contract.

        lb sources (each a true lower bound on tw(g)): the preprocess
        bound, the fold of finished blocks (their exact widths), the
        current block's ``plan.lb``, and its refuted rungs (k0..k-1
        infeasible ⇒ tw ≥ k — only when k0 was not forced above the
        genuine bound and no state was dropped).  ub sources (each a
        true upper bound per part; the instance ub is their max):
        finished blocks' widths (folded), the current block's heuristic
        ``plan.ub``, and n-1 for blocks not yet planned.  The serve
        scheduler clamps these monotone against the previously streamed
        pair; the deadline/cancel paths resolve with them directly."""
        lb = self.pre.lb if self.pre is not None else 0
        ub_parts = [0]
        if self.fold is not None:
            lb = max(lb, self.fold.lbs)
            if self.fold.exact:
                lb = max(lb, self.fold.width)
            ub_parts.append(self.fold.width)
        run = self.run
        if run is not None:
            lb = max(lb, run.plan.lb)
            if not run.plan.forced and not run.any_inexact:
                lb = max(lb, run.k)
            ub_parts.append(run.plan.ub)
        ub_parts.extend(p.n - 1 for p in self.parts[self.bi:])
        return lb, max(ub_parts)

    def partial(self) -> tuple:
        """``(expanded, per_k)`` accounted so far: finished blocks' fold
        plus the current block's in-progress ladder — the best-so-far
        work accounting a preempted (deadline) or abandoned (cancel)
        request reports instead of nothing."""
        run = self.run
        if self.fold is None:          # use_preprocess=False: one block,
            if run is None:            # per_k keyed directly by k
                return 0, {}
            return run.expanded, dict(run.per_k)
        expanded = self.fold.expanded
        per_k = dict(self.fold.per_k)
        if run is not None:
            expanded += run.expanded
            per_k[run.plan.g.name] = dict(run.per_k)
        return expanded, per_k

    def anytime_result(self, lb: Optional[int] = None,
                       ub: Optional[int] = None) -> SolveResult:
        """Resolve the instance *now* with its monotone best-so-far
        bounds (Tamaki's anytime framing, PAPERS.md): ``width=ub``
        (a heuristic order of that width exists), ``exact=False``, and
        the partial ``expanded``/``per_k``.  ``lb``/``ub`` default to
        ``bounds()``; the scheduler passes its stream-clamped pair so
        the terminal result agrees with the streamed events."""
        b_lb, b_ub = self.bounds()
        lb = b_lb if lb is None else lb
        ub = b_ub if ub is None else ub
        expanded, per_k = self.partial()
        return SolveResult(ub, False, lb, ub, expanded,
                           time.time() - self.t0, None, per_k)

    def _fold(self, bres: SolveResult, name: str, idx: int):
        if self.reconstruct:
            self.block_orders[idx] = bres.order
        if not self.use_pre:
            self.result = dataclasses.replace(
                bres, time_sec=time.time() - self.t0)
            return
        self.fold.add(name, bres)

    def _advance(self):
        """Start the next runnable block, or finish the instance."""
        while self.run is None and self.result is None:
            if self.bi >= len(self.parts):
                if self.use_pre:
                    order = None
                    if self.reconstruct:
                        order = stitch_and_verify(
                            self.g, self.pre, self.block_orders,
                            self.fold.width)
                    self.result = self.fold.result(
                        time.time() - self.t0, order)
                return
            part = self.parts[self.bi]
            idx = self.bi
            self.bi += 1
            if self.use_pre and self.fold.skip(part):
                continue
            plan = plan_block(part, **self.plan_kw)
            self.tracker.count(paths_flows=plan.paths_flows,
                               paths_pairs=plan.paths_pairs)
            if plan.result is not None:
                self._fold(plan.result, part.name, idx)
                continue
            self.run = _Run(plan, k=plan.k0, idx=idx)

    def _certify(self, plan: BlockPlan, k: int) -> Optional[list]:
        """Replay the winning rung on the host loop for level snapshots
        and backtrack an elimination order (uncounted — see class doc)."""
        kw = dict(self.recon_kw)
        if kw.get("cap") is None:
            kw["cap"] = batch_lib.plan_capacity(
                plan.g.n, block=kw.get("block", 32),
                cap_max=kw.pop("cap_max", batch_lib.DEFAULT_CAP))
        else:
            kw.pop("cap_max", None)
        res = solver.decide(plan.graph_at(k), k, plan.clique,
                                keep_levels=True, **kw)
        return solver.reconstruct_order(plan.graph_at(k), k,
                                            plan.clique, res.levels)

    def finish_block(self, k_found: Optional[int]):
        run = self.run
        plan = run.plan
        if k_found is not None:
            order = (self._certify(plan, k_found)
                     if self.reconstruct else None)
            bres = SolveResult(
                k_found, plan.exact_at(k_found, run.any_inexact), plan.lb,
                plan.ub, run.expanded, 0.0, order, run.per_k)
        else:
            bres = SolveResult(
                plan.ub, not run.any_inexact, plan.lb, plan.ub,
                run.expanded, 0.0, plan.ub_order, run.per_k)
        self.run = None
        self._fold(bres, plan.g.name, run.idx)
        self._advance()

    def feed(self, k: int, res: LaneResult) -> bool:
        """Consume one rung verdict with sequential-ladder accounting.

        Returns ``False`` once the block finished on this verdict (a
        speculative caller must discard its remaining rungs *uncounted* —
        the sequential ladder never ran them), ``True`` while the ladder
        continues.  This is the one accounting path of every driver, so
        ``expanded``/``per_k`` cannot drift between them."""
        run = self.run
        run.expanded += res.expanded
        run.per_k[k] = {"feasible": res.feasible, "inexact": res.inexact,
                        "expanded": res.expanded}
        counts = dict(rungs_decided=1, expanded=res.expanded)
        if res.inexact:
            counts["rung_overflows"] = 1
        self.tracker.count(**counts)
        if res.feasible:
            self.finish_block(k)
            return False
        if res.inexact:
            # a state leading to a width-k order may have been dropped:
            # anything concluded beyond this k is a candidate value only
            # (paper: struck-through entries); the ladder keeps going
            run.any_inexact = True
        run.k = k + 1
        if run.k >= run.plan.ub:
            self.finish_block(None)
            return False
        return True

    def improve_bounds(self, lb: Optional[int] = None,
                       ub: Optional[int] = None,
                       ub_order: Optional[list] = None) -> dict:
        """Clamp anytime heuristic bounds into the current block's ladder
        (``core.bounds_engine`` improvers; monotone tighten only).

        A tighter ub (with its replayable order certificate) shortens the
        remaining ladder; a tighter lb skips rungs the minor argument has
        already refuted — ``run.k`` jumps forward and the skipped rungs
        are never dispatched, exactly as if ``plan_block`` had known the
        bound at admission.  Neither side can change the final verdict:
        when the clamped ladder closes (``run.k >= plan.ub``) the block
        resolves through the same ``finish_block(None)`` path the
        exhausted ladder uses, with both sides certificate-backed.
        Returns ``{lb_improved, ub_improved, rungs_skipped, finished}``
        (``finished`` = the whole *instance* resolved); hints without a
        certificate order, stale hints, and loosenings are ignored."""
        out = dict(lb_improved=False, ub_improved=False, rungs_skipped=0,
                   finished=False)
        run = self.run
        if run is None or self.result is not None:
            return out
        plan = run.plan
        if ub is not None and ub_order is not None and int(ub) < plan.ub:
            out["rungs_skipped"] += plan.ub - max(int(ub), run.k)
            plan.ub = int(ub)
            plan.ub_order = list(ub_order)
            out["ub_improved"] = True
        if lb is not None and int(lb) > plan.lb:
            plan.lb = min(int(lb), plan.ub)
            out["lb_improved"] = True
            if plan.lb > run.k:
                out["rungs_skipped"] += plan.lb - run.k
                run.k = plan.lb
        if run.k >= plan.ub:
            self.finish_block(None)
        out["finished"] = self.result is not None
        return out


# -------------------------------------------------------------- round loop

def _run_rounds(insts: Sequence[InstanceState], *, lanes: int,
                speculate: int, n_pad: Optional[int] = None,
                shards: int = 1, mesh=None, verbose: bool = False,
                tracker=None, **decide_kw) -> None:
    """Drive every instance to its result, one round at a time.

    Each round packs every live instance's next ``speculate`` rungs and
    decides them: on a device mesh (``mesh``, ``distributed``), as one
    sharded dispatch per rung (``shards > 1``, ``shard``), or as
    multi-lane dispatches of up to ``lanes`` lanes (``batch``).  Verdicts
    are fed back in rung order; a rung above the one that finished its
    block is discarded uncounted — the sequential ladder never ran it.
    ``decide_kw`` goes to the chosen dispatch."""
    rnd = 0
    while True:
        live = [inst for inst in insts if inst.run is not None]
        if not live:
            return
        sched = []
        lane_list: List[Lane] = []
        for inst in live:
            run = inst.run
            ks = list(range(run.k, min(run.k + speculate, run.plan.ub)))
            sched.append((inst, ks))
            lane_list.extend(
                Lane(run.plan.graph_at(kk), kk, tuple(run.plan.clique))
                for kk in ks)
        if verbose:
            print(f"[ladder] round {rnd}: {len(lane_list)} lanes over "
                  f"{len(live)} instances", flush=True)
        results: List[LaneResult] = []
        if mesh is not None:
            for lane in lane_list:
                results += dist_lib.decide_launch(
                    lane.g, lane.k, lane.clique, mesh, tracker=tracker,
                    **decide_kw).result()
        elif shards > 1:
            for lane in lane_list:
                results += shard_lib.decide_sharded_async(
                    lane.g, lane.k, lane.clique, shards=shards,
                    tracker=tracker, **decide_kw).result()
        else:
            for lo in range(0, len(lane_list), lanes):
                group = lane_list[lo:lo + lanes]
                results += batch_lib.decide_lanes_async(
                    group, n_pad=n_pad,
                    lane_pad=min(lanes, batch_lib.pow2_at_least(len(group))),
                    tracker=tracker, **decide_kw).result()
        pos = 0
        for inst, ks in sched:
            name = inst.run.plan.g.name
            rungs = results[pos:pos + len(ks)]
            pos += len(ks)
            for kk, res in zip(ks, rungs):
                if verbose:
                    print(f"  [{name}] k={kk} feasible={res.feasible} "
                          f"expanded={res.expanded} inexact={res.inexact}",
                          flush=True)
                if not inst.feed(kk, res):
                    break
        rnd += 1


# ----------------------------------------------------------------- drivers

def solve(g: Graph, *, cap: Optional[int] = None, block: int = 1 << 11,
          mode: str = "sort", use_mmw: bool = False, m_bits: int = 1 << 24,
          k_hashes: int = bloom.DEFAULT_K, schedule: Optional[str] = None,
          use_clique: bool = True, use_paths: bool = True,
          use_preprocess: bool = True, reconstruct: bool = False,
          start_k: Optional[int] = None, verbose: bool = False,
          backend: str = "jax", use_simplicial: bool = False,
          lanes: int = 1, shards: int = 1,
          donate_ratio: Optional[float] = None,
          heuristics: int = 0, seed: int = 0, tracker=None) -> SolveResult:
    """Compute the treewidth of ``g``: the round loop over one instance.

    ``mode="sort"`` (default) dedups exactly; ``mode="bloom"`` reproduces
    the paper's Monte-Carlo Bloom filter.  Overflow of a frontier buffer
    drops states and marks the result inexact (the paper's * semantics).

    ``cap`` bounds the frontier buffer (rows per level).  The default
    ``cap=None`` sizes it per rung with ``batch.plan_capacity``: the
    block's drop-free state bound, clamped to ``batch.DEFAULT_CAP``.
    Pass an explicit power of two to pin it.
    ``backend`` selects the op implementations through the registry
    (``repro.core.backend``): "jax" reference or fused "pallas" kernels.
    ``schedule=None`` resolves to ``backend.default_schedule``.
    ``lanes > 1`` turns the ladder speculative: each dispatch decides
    ``lanes`` consecutive k concurrently through the multi-lane engine
    (``core.batch``) — same results, fewer dispatches.
    ``shards > 1`` splits each rung's *frontier* across S concurrent
    workers instead (``core.shard``: single-writer ownership routing,
    threshold work donation tuned by ``donate_ratio``) — same results
    with S× the aggregate frontier capacity; forces ``lanes=1``.
    ``heuristics > 0`` runs that many anytime bounds-improver rounds
    (``core.bounds_engine``) before each block's ladder; ``seed`` pins
    every heuristic for bit-reproducible plans.
    ``reconstruct=True`` returns a certified elimination order: each
    block's winning rung is replayed once on the host loop, uncounted,
    and the block orders are stitched through the preprocess maps.
    To batch *across* instances, see ``solve_many``; to serve a
    concurrent request stream, see ``repro.serve.twscheduler``."""
    if schedule is None:
        schedule = backend_lib.default_schedule(backend)
    lanes, shards = int(lanes), int(shards)
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits, lanes=lanes, shards=shards)
    if shards > 1:
        lanes = 1         # sharding takes the whole device
    decide_kw = dict(cap=cap, block=block, mode=mode, use_mmw=use_mmw,
                     m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
                     backend=backend, use_simplicial=use_simplicial)
    tr = telemetry.get(tracker)
    inst = InstanceState(
        g, use_preprocess=use_preprocess,
        plan_kw=dict(use_clique=use_clique, use_paths=use_paths,
                     start_k=start_k, heuristics=heuristics, seed=seed),
        reconstruct=reconstruct, recon_kw=decide_kw, tracker=tr)
    shard_kw = dict(donate_ratio=donate_ratio) if shards > 1 else {}
    _run_rounds([inst], lanes=lanes, speculate=lanes, shards=shards,
                verbose=verbose, tracker=tr, **decide_kw, **shard_kw)
    return inst.result


def solve_many(graphs: Sequence[Graph], *, cap: Optional[int] = None,
               block: int = 1 << 11, mode: str = "sort",
               use_mmw: bool = False, m_bits: int = 1 << 24,
               k_hashes: int = bloom.DEFAULT_K,
               schedule: Optional[str] = None, use_clique: bool = True,
               use_paths: bool = True, use_preprocess: bool = True,
               reconstruct: bool = False,
               start_k: Optional[int] = None, verbose: bool = False,
               backend: str = "jax", use_simplicial: bool = False,
               lanes: int = batch_lib.DEFAULT_MAX_LANES,
               speculate: int = 1,
               budget_bytes=None) -> List[SolveResult]:
    """Solve a whole suite with cross-instance lane batching.

    Returns one ``SolveResult`` per input, in input order, with the
    exact widths/exactness/bounds/``per_k``/``expanded`` the sequential
    ``[solve(g) for g in graphs]`` loop produces — subject to the two
    padding caveats of ``core.batch``: under ``use_mmw=True`` the padded
    lanes may expand a superset (verdicts unchanged), and under
    ``mode="bloom"`` a lane padded into a larger word count than its
    sequential run (instances straddling a multiple of 32 vertices) draws
    a different Monte-Carlo false-positive set, so its width/exactness
    carry the usual Bloom-mode probabilistic guarantee rather than
    bit-parity with the sequential run.  The default configuration
    (sort-mode dedup, no MMW) is exactly parity-pinned.  Every round
    packs all instances' current deepening rungs into multi-lane
    dispatches of up to ``lanes`` lanes.  ``speculate > 1`` additionally
    lets each instance occupy that many consecutive-k lanes per round.

    ``cap=None`` (default) sizes the suite's shared per-lane buffer with
    ``plan_capacity`` (drop-free bound of the largest block, clamped to
    ``DEFAULT_CAP`` / ``budget_bytes``) instead of one fixed worst-case
    buffer — small preprocessed blocks stop paying for 2^17 rows they
    can never fill, and the parity guarantees above still hold.

    ``reconstruct=True`` certifies every result with a stitched
    elimination order exactly like ``solve(reconstruct=True)``.

    Runnable example (suite batching; for a *concurrent request stream*
    with per-request knobs and streaming, use the serve scheduler —
    DESIGN.md §10/§11)::

        from repro.core import graph, ladder
        res = ladder.solve_many([graph.myciel(4), graph.petersen()],
                                lanes=8)
        [r.width for r in res]            # -> [10, 4]
    """
    if schedule is None:
        schedule = backend_lib.default_schedule(backend)
    lanes = int(lanes)
    speculate = max(1, int(speculate))
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits, lanes=lanes)
    decide_kw = dict(cap=cap, block=block, mode=mode, use_mmw=use_mmw,
                     m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
                     backend=backend, use_simplicial=use_simplicial)
    plan_kw = dict(use_clique=use_clique, use_paths=use_paths,
                   start_k=start_k)
    insts = [InstanceState(g, use_preprocess=use_preprocess,
                           plan_kw=plan_kw, reconstruct=reconstruct,
                           recon_kw=decide_kw) for g in graphs]
    n_pad = max([i.max_n() for i in insts], default=1)
    if cap is None:
        # resolve ONE plan for the whole suite (largest block wins)
        # instead of per dispatch group: per-group caps would mint a new
        # jit signature every time group membership changes, and the
        # vmapped lane program is expensive to compile.  Still <= the old
        # fixed default, and all-small suites keep the full footprint cut.
        w = bitset.n_words(n_pad)
        cap = max([batch_lib.plan_capacity(
            p.n, w, lanes=lanes, block=block, budget_bytes=budget_bytes)
            for i in insts for p in i.parts], default=32)
    _run_rounds(insts, lanes=lanes, speculate=speculate, n_pad=n_pad,
                verbose=verbose, budget_bytes=budget_bytes,
                **dict(decide_kw, cap=cap))
    return [inst.result for inst in insts]


def solve_distributed(g: Graph, mesh, *, cap_local: int = 1 << 14,
                      block: int = 1 << 8, use_mmw: bool = False,
                      use_simplicial: bool = False,
                      schedule: str = "doubling", backend: str = "jax",
                      use_clique: bool = True, use_paths: bool = True,
                      use_preprocess: bool = True, verbose: bool = False,
                      donate_ratio: Optional[float]
                      = dist_lib.DEFAULT_DONATE_RATIO,
                      tracker=None) -> SolveResult:
    """The round loop over one instance whose rungs run on a device mesh
    (``distributed.decide_launch``: the frontier sharded over the mesh,
    exact owner dedup).  Width, bounds, ``expanded`` and ``per_k`` as
    ``solve``; no reconstruction.  Checkpoint and elastic restart of one
    rung live on ``distributed.decide_distributed``."""
    tr = telemetry.get(tracker)
    inst = InstanceState(
        g, use_preprocess=use_preprocess,
        plan_kw=dict(use_clique=use_clique, use_paths=use_paths,
                     start_k=None),
        tracker=tr)
    _run_rounds([inst], lanes=1, speculate=1, mesh=mesh, verbose=verbose,
                tracker=tr, cap_local=cap_local, block=block,
                use_mmw=use_mmw, use_simplicial=use_simplicial,
                schedule=schedule, backend=backend,
                donate_ratio=donate_ratio)
    return inst.result
