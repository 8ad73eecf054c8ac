"""Batched multi-lane decide engine: one dispatch decides B subproblems.

The fused engine (``core.engine``) already keeps a single ``decide(g, k)``
on device, but the iterative-deepening driver and suite workloads still
issue every decide as its own program — early levels and small instances
leave the device nearly idle.  This module adds the missing batching axis
(component-aware parallel branching in the GPU-vertex-cover sense: run
independent subproblems concurrently until each saturates the device):

  * ``_lanes_decide`` vmaps ``engine.decide_loop`` over a leading lane
    axis.  Each lane carries its own padded ``(adj, allowed, k, target)``
    and ``Frontier`` slice; the while_loop batching rule folds per-lane
    early exit into the masked loop condition (a finished lane's carry is
    frozen by ``select`` while the others keep stepping), so every lane's
    result is bit-identical to running it alone.
  * ``decide_lanes`` is the host entry: pad, pack, one dispatch, one sync.
  * ``decide_batch(g, ks)`` — speculative deepening: decide
    ``k, k+1, ..`` for one graph concurrently (used by
    ``solver.solve_block(lanes=...)``; smallest feasible rung wins).
  * ``solve_many(graphs)`` — suite driver: pads instances/biconnected
    blocks to a common ``(n_max, W)`` and schedules lanes across the whole
    suite, replicating ``solver.solve``'s per-instance semantics exactly
    (same ``plan_block`` bounds, same skip rule, same accounting).
  * ``InstanceState`` — the per-request unit those drivers (and the serve
    scheduler, ``repro.serve.twscheduler``) advance rung by rung.
  * ``plan_capacity`` — the memory model: right-sizes per-lane frontier
    buffers from the block's state space, the chunk geometry and an
    optional device-memory budget instead of the fixed worst-case ``cap``
    (DESIGN.md §10).

Padding semantics: a lane of true size ``n_g`` is embedded at the bottom
of the common ``n_max`` index space; padding vertices are isolated in
``adj`` and cleared from ``allowed``, so they are never feasible
candidates and never perturb closures — the DP explores exactly the real
graph and frontier buffers match the unpadded run bit for bit (padded
state words are zero, so sort order is preserved too).  Two documented
caveats, both absent when lanes share one true ``n`` (e.g. speculative
deepening): (1) MMW pruning sees the padding vertices as isolated
degree-0 rows, which can only *weaken* the bound — verdicts are
unchanged, but ``expanded`` under ``use_mmw=True`` may exceed the
sequential count; (2) Bloom hashes cover all ``W`` words, so a lane
padded to a larger word count draws a different (still Monte-Carlo
correct) false-positive set than its sequential run.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import backend as backend_lib
from . import bitset, bloom
from . import engine as engine_lib
from . import frontier as frontier_lib
from . import preprocess as preprocess_lib
from . import telemetry
from .graph import Graph

U32 = jnp.uint32

# default lane width of one dispatch: enough to cover a suite round or a
# deepening ladder without blowing the frontier-buffer footprint
# (B * cap * W words resident per dispatch)
DEFAULT_MAX_LANES = 8

# the historical fixed frontier capacity (solver.solve's old default).
# ``cap=None`` everywhere now means "plan_capacity, clamped to this":
# callers that want the old behaviour pass the constant explicitly.
DEFAULT_CAP = 1 << 17


def plan_capacity(n: int, w: Optional[int] = None, *, lanes: int = 1,
                  block: int = 1 << 11, cap_max: int = DEFAULT_CAP,
                  budget_bytes=None) -> int:
    """Right-size the per-lane frontier capacity for an ``n``-vertex block.

    Replaces the fixed ``cap`` default with the smallest power-of-two
    buffer that provably never drops a state the fixed buffer would have
    kept, so auto-sized runs stay bit-identical to fixed-``cap`` runs
    (DESIGN.md §10).  The bound: a level holds at most ``C(n, l)``
    distinct size-``l`` subsets, so with exact inter-level dedup the
    append stream of one level is at most ``count * n <=
    n * C(n, floor(n/2))`` rows — a buffer that large can never overflow,
    and above ``cap_max`` the plan clamps to ``cap_max`` exactly like the
    fixed default did.  Small preprocessed blocks are where this bites:
    an ``n=10`` block plans 4096 rows instead of 2^17, cutting the
    multi-lane pool footprint ~32x per lane.

    The planned cap never goes below ``block`` (chunk geometry — and with
    it Bloom-mode insert order — must match a fixed-``cap`` run of the
    same ``block``), nor below 32 (the engine's smallest adaptive chunk).

    ``budget_bytes`` optionally bounds the whole ``lanes``-wide pool:
    ``lanes * cap * W * 4`` bytes is kept under the budget (pass
    ``w = bitset.n_words(n_padded)`` for padded dispatches, and
    ``budget_bytes="auto"`` to read ``backend.device_memory_budget()``).
    A binding budget may reintroduce drops — runs stay correct, but carry
    the usual overflow inexactness instead of the parity guarantee.

    Runnable example::

        from repro.core import batch
        batch.plan_capacity(10, block=1 << 11)            # -> 4096
        batch.plan_capacity(25)                           # -> 131072 (2^17)
        batch.plan_capacity(14, 1, lanes=8,               # pool under a
                            budget_bytes=8 * 1024 * 4)    # 32 KiB budget
    """
    if n <= 1:
        need = 1
    else:
        need = n * math.comb(n, n // 2) + 1
    cap_hi = _pow2_floor(cap_max)      # an explicit cap_max is a ceiling:
    cap = min(_pow2_at_least(need), cap_hi)   # round DOWN, never past it
    cap = max(cap, 32, _pow2_at_least(min(block, cap_hi)))
    if budget_bytes == "auto":
        budget_bytes = backend_lib.device_memory_budget()
    if budget_bytes is not None:
        row_bytes = 4 * max(1, w if w is not None else bitset.n_words(n))
        afford = int(budget_bytes) // (max(1, lanes) * row_bytes)
        cap = max(32, min(cap, _pow2_floor(afford)))
    return cap


@dataclasses.dataclass(frozen=True)
class Lane:
    """One subproblem: decide tw(g) <= k, skipping ``clique`` (never
    eliminated — some optimal order ends with the max clique)."""
    g: Graph
    k: int
    clique: tuple = ()


@dataclasses.dataclass
class LaneResult:
    """Per-lane verdict; field-compatible with ``solver.DecideResult``
    minus the host level snapshots (lanes never keep levels)."""
    feasible: bool
    inexact: bool
    expanded: int


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _pow2_floor(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


LANE_AXIS = "lanes"


@functools.partial(
    jax.jit,
    static_argnames=("n", "cap", "block", "mode", "use_mmw", "m_bits",
                     "k_hashes", "schedule", "backend", "use_simplicial"))
def _lanes_decide(adj, allowed, k, target, fr, *, n, cap, block, mode,
                  use_mmw, m_bits, k_hashes, schedule, backend,
                  use_simplicial):
    """``engine.decide_loop`` vmapped over the leading lane axis.

    adj (B, n, W) / allowed (B, W) / k, target (B,) / fr with lane-leading
    leaves.  One compiled program, one launch, B verdicts.  The axis is
    named (``LANE_AXIS``) so that the mid-level refill's predicate is
    one value for every lane (``engine.refill``)."""
    def one_lane(a, al, kk, tt, f):
        return engine_lib.decide_loop(
            a, al, kk, tt, f, n=n, cap=cap, block=block, mode=mode,
            use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
            schedule=schedule, backend=backend,
            use_simplicial=use_simplicial, lane_axis=LANE_AXIS)
    return jax.vmap(one_lane, axis_name=LANE_AXIS)(adj, allowed, k, target,
                                                   fr)


def _pack_lanes(lanes: Sequence[Lane], n_max: int, w: int):
    """Host-side padding: embed every lane in the common (n_max, W) space.

    Padding vertices stay isolated (zero adjacency rows) and are cleared
    from ``allowed``; ``target`` counts the lane's *true* levels, so the
    loop runs exactly as long as the unpadded decide would.  A lane whose
    target is <= 0 is trivially feasible and exits before its first level
    — the batched mirror of ``solver.decide``'s early return."""
    b = len(lanes)
    adj = np.zeros((b, n_max, w), dtype=np.uint32)
    allowed = np.zeros((b, w), dtype=np.uint32)
    ks = np.zeros((b,), dtype=np.int32)
    targets = np.zeros((b,), dtype=np.int32)
    for i, lane in enumerate(lanes):
        p = lane.g.packed()
        adj[i, :lane.g.n, :p.shape[1]] = p
        allowed[i] = bitset.np_allowed(lane.g.n, lane.clique, w)
        ks[i] = lane.k
        targets[i] = max(0, lane.g.n - max(lane.k + 1, len(lane.clique)))
    return adj, allowed, ks, targets


_TRIVIAL = Graph(1, np.zeros((1, 1), dtype=bool), "pad")


def _empty_dispatch() -> engine_lib.DispatchHandle:
    """A no-op handle: zero lanes, nothing dispatched, nothing to sync."""
    return engine_lib.DispatchHandle((), lambda host: [],
                                     _result=[], _done=True)


def decide_lanes_async(lanes: Sequence[Lane], *, cap: Optional[int] = None,
                       block: int, mode: str,
                       use_mmw: bool, m_bits: int, k_hashes: int,
                       schedule: str,
                       backend: str = "jax", use_simplicial: bool = False,
                       n_pad: Optional[int] = None,
                       lane_pad: Optional[int] = None,
                       cap_max: int = DEFAULT_CAP,
                       budget_bytes=None,
                       tracker=None) -> engine_lib.DispatchHandle:
    """Enqueue one multi-lane dispatch without blocking on its verdicts.

    The vmapped program is dispatched (counted) and the per-lane result
    arrays are held on device in the returned
    ``engine.DispatchHandle``; ``handle.result()`` performs the single
    deferred host sync and yields the ``List[LaneResult]``
    ``decide_lanes`` would have returned.  Between launch and result the
    host is free — the async solve service (``repro.serve.twscheduler``)
    admits and plans newly arrived requests there, so they are packed
    into the *next* dispatch instead of waiting for an idle pool.

        h = batch.decide_lanes_async([batch.Lane(g, 3)], block=32,
                                     mode="sort", use_mmw=False,
                                     m_bits=1 << 12, k_hashes=4,
                                     schedule="while")
        ...                      # host-side work overlaps the device
        [verdict] = h.result()   # the only host sync

    All knobs and padding/auto-``cap`` semantics are exactly
    ``decide_lanes``'s (which is now just launch + immediate result).
    """
    if not lanes:
        return _empty_dispatch()
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits, lanes=len(lanes))
    live = len(lanes)
    n_max = max(lane.g.n for lane in lanes)
    if n_pad is not None:
        if n_pad < n_max:
            raise ValueError(f"n_pad ({n_pad}) < largest lane n ({n_max})")
        n_max = n_pad
    n_max = max(1, n_max)
    if lane_pad is not None and lane_pad > live:
        lanes = list(lanes) + [Lane(_TRIVIAL, 0)] * (lane_pad - live)
    w = bitset.n_words(n_max)
    if cap is None:
        cap = max(plan_capacity(lane.g.n, w, lanes=len(lanes), block=block,
                                cap_max=cap_max, budget_bytes=budget_bytes)
                  for lane in lanes)
    block = engine_lib.validate_geometry(cap, block)

    tr = telemetry.get(tracker)
    slots = len(lanes)
    with tr.span("tw.pack"):
        adj, allowed, ks, targets = _pack_lanes(lanes, n_max, w)
        args = (jnp.asarray(adj), jnp.asarray(allowed), jnp.asarray(ks),
                jnp.asarray(targets),
                frontier_lib.lane_frontiers(slots, cap, w))
    with tr.span("tw.enqueue"):
        out_fr, levels, expanded, dropped, refills, appended = _lanes_decide(
            *args, n=n_max, cap=cap, block=block, mode=mode,
            use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
            schedule=schedule, backend=backend,
            use_simplicial=use_simplicial)
    tr.count(dispatches=1)

    def finalize(host):
        counts_h, exp_h, drop_h, lev_h, ref_h, app_h = host
        out = [LaneResult(bool(counts_h[i] > 0), bool(drop_h[i] > 0),
                          int(exp_h[i])) for i in range(live)]
        # per-lane work accounting for the batch layer: how many real
        # lanes this dispatch decided out of the lane slots it padded
        # them to, the states they expanded against the frontier rows
        # their full-cap buffers held (levels x cap), the levels they
        # ran, the mid-level refills and appended rows of those levels,
        # and how many hit the overflow (inexact) path
        lane_levels = int(np.sum(lev_h[:live]))
        tr.count(lanes_decided=live, lane_slots=slots,
                 lane_expanded=sum(r.expanded for r in out),
                 lane_row_slots=cap * lane_levels, lane_levels=lane_levels,
                 refills=int(np.sum(ref_h[:live])),
                 appended_rows=int(np.sum(app_h[:live])),
                 lane_overflows=sum(1 for r in out if r.inexact))
        return out

    return engine_lib.DispatchHandle(
        (out_fr.count, expanded, dropped, levels, refills, appended),
        finalize, tracker=tr)


def decide_lanes(lanes: Sequence[Lane], *, cap: Optional[int] = None,
                 block: int, mode: str,
                 use_mmw: bool, m_bits: int, k_hashes: int, schedule: str,
                 backend: str = "jax", use_simplicial: bool = False,
                 n_pad: Optional[int] = None,
                 lane_pad: Optional[int] = None,
                 cap_max: int = DEFAULT_CAP,
                 budget_bytes=None,
                 tracker=None) -> List[LaneResult]:
    """Decide every lane in one dispatch; one host sync for all verdicts.

    ``n_pad`` pins the padded vertex count (callers batching many rounds
    pass a global n_max so every round hits the same compiled program);
    ``lane_pad`` rounds the lane axis up with trivial lanes for the same
    reason (compiled-program cache keyed on B).

    ``cap=None`` sizes the shared per-lane buffer with ``plan_capacity``:
    the largest lane's drop-free bound, clamped to ``cap_max`` (and to
    ``budget_bytes`` over the whole pool when given) — results stay
    bit-identical to a fixed-``cap`` dispatch per the plan's guarantee.

    Blocking form of ``decide_lanes_async`` — launch + immediate
    ``result()``.
    """
    return decide_lanes_async(
        lanes, cap=cap, block=block, mode=mode, use_mmw=use_mmw,
        m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
        backend=backend, use_simplicial=use_simplicial, n_pad=n_pad,
        lane_pad=lane_pad, cap_max=cap_max,
        budget_bytes=budget_bytes, tracker=tracker).result()


def decide_batch(g: Graph, ks: Sequence[int], clique: Sequence[int] = (),
                 *, graphs: Optional[Sequence[Graph]] = None,
                 cap: Optional[int] = None,
                 block: int, mode: str, use_mmw: bool, m_bits: int,
                 k_hashes: int, schedule: str, backend: str = "jax",
                 use_simplicial: bool = False,
                 tracker=None) -> List[LaneResult]:
    """Speculative deepening primitive: decide tw(g) <= k for several k in
    one dispatch.

    ``graphs`` optionally overrides the graph per rung — the deepening
    driver passes the paths-rule-augmented ``G_k`` for each k (rule 2
    admits more edges at higher k, so the lanes genuinely differ).  All
    lanes share the true ``n``, so results are bit-identical to the
    sequential ``decide`` loop for every mode/pruning combination."""
    if graphs is not None and len(graphs) != len(ks):
        raise ValueError("graphs must align with ks")
    lanes = [Lane(graphs[i] if graphs is not None else g, int(k),
                  tuple(clique)) for i, k in enumerate(ks)]
    return decide_lanes(lanes, cap=cap, block=block, mode=mode,
                        use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
                        schedule=schedule, backend=backend,
                        use_simplicial=use_simplicial, tracker=tracker)


# ----------------------------------------------------------- suite driver

@dataclasses.dataclass
class _Run:
    """Iterative deepening in progress on one block (mirrors the ladder
    state of ``solver.solve_block``)."""
    plan: object                  # solver.BlockPlan
    k: int
    idx: int = 0                  # index into the preprocess block list
    expanded: int = 0
    any_inexact: bool = False
    per_k: dict = dataclasses.field(default_factory=dict)


class InstanceState:
    """One input graph's scheduler state: the solve()-shaped fold over its
    preprocessed blocks (``solver.SuiteFold`` — the same accumulator
    ``solve`` uses, so the two drivers cannot drift), advanced block by
    block as lane verdicts are fed back.

    This is the per-request unit of both lane drivers: ``solve_many``
    walks a whole suite of them, and the serve scheduler
    (``repro.serve.twscheduler``) keeps one per admitted request, feeding
    each slot's rung verdict after every shared dispatch.  ``result`` is
    set (a ``solver.SolveResult``) once the instance is decided; until
    then ``run`` names the block rung currently occupying a lane.

    ``reconstruct=True`` additionally certifies the result with an
    elimination order: when a block's winning rung is found, that single
    rung is replayed once on the host engine (``keep_levels=True``) to
    snapshot its levels — the replay is *not* counted into ``expanded``,
    which keeps the accounting bit-identical to ``solver.solve`` (the
    sequential path also expands the winning rung exactly once) — and the
    block orders are stitched through the preprocess maps exactly like
    ``solve(reconstruct=True)``.  ``recon_kw`` carries the decide kwargs
    for that replay (``cap=None`` re-plans per block via
    ``plan_capacity``, matching the sequential auto-sizing)."""

    def __init__(self, g: Graph, solver_lib, *, use_preprocess: bool,
                 plan_kw: dict, reconstruct: bool = False,
                 recon_kw: Optional[dict] = None, tracker=None):
        self.g = g
        self.solver = solver_lib
        self.plan_kw = plan_kw
        # per-request telemetry scope (the serve scheduler passes each
        # request's child tracker so rung/expanded counts attribute to it
        # and roll up into the pool totals); NULL here, not the root —
        # suite drivers opt in explicitly
        self.tracker = telemetry.NULL if tracker is None else tracker
        self.reconstruct = reconstruct
        self.recon_kw = dict(recon_kw or {})
        self.t0 = time.time()
        self.result: Optional[object] = None     # solver.SolveResult
        self.run: Optional[_Run] = None
        self.pre = None                          # preprocess.Preprocessed
        self.use_pre = use_preprocess
        self.bi = 0
        if g.n == 0:
            self.parts: list = []
            self.fold = None
            self.block_orders: list = []
            self.result = solver_lib.SolveResult(0, True, 0, 0, 0, 0.0,
                                                 [], {})
            return
        if use_preprocess:
            self.pre = preprocess_lib.preprocess(g)
            self.parts = [b.g for b in self.pre.blocks]
            self.fold = solver_lib.SuiteFold.start(self.pre.lb)
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
        if self.fold is None:          # use_preprocess=False: solve_block
            if run is None:            # shape — per_k keyed directly by k
                return 0, {}
            return run.expanded, dict(run.per_k)
        expanded = self.fold.expanded
        per_k = dict(self.fold.per_k)
        if run is not None:
            expanded += run.expanded
            per_k[run.plan.g.name] = dict(run.per_k)
        return expanded, per_k

    def anytime_result(self, lb: Optional[int] = None,
                       ub: Optional[int] = None):
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
        return self.solver.SolveResult(ub, False, lb, ub, expanded,
                                       time.time() - self.t0, None, per_k)

    def _fold(self, bres, name: str, idx: int):
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
                        order = self.solver.stitch_and_verify(
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
            plan = self.solver.plan_block(part, **self.plan_kw)
            self.tracker.count(paths_flows=plan.paths_flows,
                               paths_pairs=plan.paths_pairs)
            if plan.result is not None:
                self._fold(plan.result, part.name, idx)
                continue
            self.run = _Run(plan, k=plan.k0, idx=idx)

    def _certify(self, plan, k: int) -> Optional[list]:
        """Replay the winning rung on the host engine for level snapshots
        and backtrack an elimination order (uncounted — see class doc)."""
        kw = dict(self.recon_kw)
        if kw.get("cap") is None:
            kw["cap"] = plan_capacity(plan.g.n, block=kw.get("block", 32),
                                      cap_max=kw.pop("cap_max", DEFAULT_CAP))
        else:
            kw.pop("cap_max", None)
        res = self.solver.decide(plan.graph_at(k), k, plan.clique,
                                 keep_levels=True, engine="host", **kw)
        return self.solver.reconstruct_order(plan.graph_at(k), k,
                                             plan.clique, res.levels)

    def finish_block(self, k_found: Optional[int]):
        run = self.run
        plan = run.plan
        if k_found is not None:
            order = (self._certify(plan, k_found)
                     if self.reconstruct else None)
            bres = self.solver.SolveResult(
                k_found, plan.exact_at(k_found, run.any_inexact), plan.lb,
                plan.ub, run.expanded, 0.0, order, run.per_k)
        else:
            bres = self.solver.SolveResult(
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
        continues.  This is the single accounting path shared by
        ``solve_many`` and the serve scheduler, so ``expanded``/``per_k``
        cannot drift from ``solver.solve_block``'s."""
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


def solve_many(graphs: Sequence[Graph], *, cap: Optional[int] = None,
               block: int = 1 << 11, mode: str = "sort",
               use_mmw: bool = False, m_bits: int = 1 << 24,
               k_hashes: int = bloom.DEFAULT_K,
               schedule: Optional[str] = None, use_clique: bool = True,
               use_paths: bool = True, use_preprocess: bool = True,
               reconstruct: bool = False,
               start_k: Optional[int] = None, verbose: bool = False,
               backend: str = "jax", use_simplicial: bool = False,
               lanes: int = DEFAULT_MAX_LANES,
               speculate: int = 1,
               budget_bytes=None) -> List[object]:
    """Solve a whole suite with cross-instance lane batching.

    Returns one ``solver.SolveResult`` per input, in input order, with the
    exact widths/exactness/bounds/``per_k``/``expanded`` the sequential
    ``[solve(g) for g in graphs]`` loop produces — subject to the two
    padding caveats in the module docstring: under ``use_mmw=True`` the
    padded lanes may expand a superset (verdicts unchanged), and under
    ``mode="bloom"`` a lane padded into a larger word count than its
    sequential run (instances straddling a multiple of 32 vertices) draws
    a different Monte-Carlo false-positive set, so its width/exactness
    carry the usual Bloom-mode probabilistic guarantee rather than
    bit-parity with the sequential run.  The default configuration
    (sort-mode dedup, no MMW) is exactly parity-pinned.  Instead of one
    dispatch per (instance, k), every scheduler round packs all
    instances' current deepening rungs into multi-lane dispatches of up to
    ``lanes`` lanes.  ``speculate > 1`` additionally lets each instance
    occupy that many consecutive-k lanes per round.

    ``cap=None`` (default) sizes each dispatch's shared per-lane buffer
    with ``plan_capacity`` (drop-free bound of the largest lane, clamped
    to ``DEFAULT_CAP`` / ``budget_bytes``) instead of one fixed
    worst-case buffer — small preprocessed blocks stop paying for 2^17
    rows they can never fill, and the parity guarantees above still hold.

    ``reconstruct=True`` certifies every result with a stitched
    elimination order exactly like ``solver.solve(reconstruct=True)``:
    each block's winning rung is replayed once on the host engine for
    level snapshots (uncounted, so ``expanded`` parity is preserved).

    Runnable example (suite batching; for a *concurrent request stream*
    with per-request knobs and streaming, use the serve scheduler —
    DESIGN.md §10/§11)::

        from repro.core import batch, graph
        res = batch.solve_many([graph.myciel(4), graph.petersen()],
                               lanes=8)
        [r.width for r in res]            # -> [10, 4]
    """
    from . import solver as solver_lib   # lazy: solver imports this module

    if schedule is None:
        schedule = "doubling" if backend == "pallas" else "while"
    lanes = int(lanes)
    speculate = max(1, int(speculate))
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits, lanes=lanes)
    decide_kw = dict(cap=cap, block=block, mode=mode, use_mmw=use_mmw,
                     m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
                     backend=backend, use_simplicial=use_simplicial,
                     budget_bytes=budget_bytes)
    plan_kw = dict(use_clique=use_clique, use_paths=use_paths,
                   start_k=start_k)
    recon_kw = dict(cap=cap, block=block, mode=mode, use_mmw=use_mmw,
                    m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
                    backend=backend, use_simplicial=use_simplicial)

    insts = [InstanceState(g, solver_lib, use_preprocess=use_preprocess,
                           plan_kw=plan_kw, reconstruct=reconstruct,
                           recon_kw=recon_kw) for g in graphs]
    n_pad = max([i.max_n() for i in insts], default=1)
    if cap is None:
        # resolve ONE plan for the whole suite (largest block wins)
        # instead of per dispatch group: per-group caps would mint a new
        # jit signature every time group membership changes, and the
        # vmapped lane program is expensive to compile.  Still <= the old
        # fixed default, and all-small suites keep the full footprint cut.
        w = bitset.n_words(n_pad)
        decide_kw["cap"] = max(plan_capacity(
            p.n, w, lanes=lanes, block=block, budget_bytes=budget_bytes)
            for i in insts for p in i.parts) if any(i.parts for i in insts) \
            else 32

    rnd = 0
    while True:
        live = [inst for inst in insts if inst.run is not None]
        if not live:
            break
        sched = []
        lane_list: list = []
        for inst in live:
            run = inst.run
            ks = list(range(run.k, min(run.k + speculate, run.plan.ub)))
            sched.append((inst, ks))
            lane_list.extend(
                Lane(run.plan.graph_at(kk), kk, tuple(run.plan.clique))
                for kk in ks)
        if verbose:
            print(f"[solve_many] round {rnd}: {len(lane_list)} lanes over "
                  f"{len(live)} instances", flush=True)
        results: list = []
        for lo in range(0, len(lane_list), lanes):
            group = lane_list[lo:lo + lanes]
            results.extend(decide_lanes(
                group, n_pad=n_pad,
                lane_pad=min(lanes, _pow2_at_least(len(group))),
                **decide_kw))
        pos = 0
        for inst, ks in sched:
            name = inst.run.plan.g.name
            rungs = results[pos:pos + len(ks)]
            pos += len(ks)
            for kk, res in zip(ks, rungs):
                if verbose:
                    print(f"  [{name}] k={kk} "
                          f"feasible={res.feasible} "
                          f"expanded={res.expanded} "
                          f"inexact={res.inexact}", flush=True)
                if not inst.feed(kk, res):
                    # block finished on this rung: rungs above it were
                    # never run sequentially — discard them uncounted
                    break
        rnd += 1
    return [inst.result for inst in insts]
