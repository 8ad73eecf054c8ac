"""Iterative-deepening treewidth solver (single device).

Structure mirrors the paper exactly (Listing 1 + §3.1 optimizations):

  for k = lb .. ub-1:                      (iterative deepening)
      G_k = G + edges{pairs with >= k+1 vertex-disjoint paths}   [rule 2]
      frontier = { {} }
      for level = 0 .. n - max(k+1, |C|) - 1:                    [rules 1,3]
          expand every S by every candidate v not in S u C,
              keeping S u {v} iff deg_S(v) <= k
          dedup (exact sort | Bloom filter)
          if frontier empty: k infeasible
      k feasible -> tw = k

Overflow of the fixed-capacity lists drops states and marks the run inexact
(identical to the paper's * semantics).  ``mode="bloom"`` reproduces the
paper's Monte-Carlo dedup; ``mode="sort"`` (default) is the exact
beyond-paper variant.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import backend as backend_lib
from . import bitset, bloom, bounds, dedup, engine as engine_lib
from . import frontier as frontier_lib
from . import expand
from . import preprocess as preprocess_lib
from . import telemetry
from .graph import Graph

U32 = jnp.uint32


# --------------------------------------------------------------- chunk step

@functools.partial(
    jax.jit,
    static_argnames=("n", "block", "mode", "use_mmw", "m_bits",
                     "k_hashes", "schedule", "backend", "use_simplicial"),
    donate_argnums=(4, 7),
)
def _chunk_step(adj, states_chunk, chunk_valid, k, out, ocount, dropped,
                filt, allowed, refills, appended, *, n, block, mode,
                use_mmw, m_bits, k_hashes, schedule, backend,
                use_simplicial=False):
    """Expand one chunk of states and append deduped children to ``out``.

    Thin jitted wrapper over ``engine.expand_chunk`` — the single shared
    implementation of the Listing-1 inner loop (also used by the fused
    device-resident engine and the distributed solver) — behind the
    fused sweep's refill rule in sort mode (``engine.refill``), with its
    ``refills`` and ``appended`` rows counted on device."""
    if mode == "sort":
        live_rows = jnp.sum(chunk_valid.astype(jnp.int32))
        out, ocount, did = engine_lib.refill(
            out, ocount, engine_lib.refill_needed(ocount, live_rows, allowed,
                                                  out.shape[0]))
        refills = refills + did
    before = ocount
    out, ocount, dropped, filt = engine_lib.expand_chunk(
        adj, states_chunk, chunk_valid, k, out, ocount, dropped, filt,
        allowed, n=n, block=block, mode=mode, use_mmw=use_mmw,
        m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
        backend=backend, use_simplicial=use_simplicial)
    return out, ocount, dropped, filt, refills, appended + ocount - before


@functools.partial(jax.jit, static_argnames=("cap",), donate_argnums=(0,))
def _final_dedup(out, ocount, cap: int):
    valid = jnp.arange(cap) < ocount
    return dedup.dedup_compact(out, valid, cap)


# --------------------------------------------------------------- level loop

@dataclasses.dataclass
class LevelStats:
    expanded: int = 0
    generated: int = 0
    dropped: int = 0


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def run_level(adj_dev, fr: frontier_lib.Frontier, k: int, allowed_dev,
              *, n: int, cap: int, block: int, mode: str, use_mmw: bool,
              m_bits: int, k_hashes: int, schedule: str,
              backend: str = "jax", use_simplicial: bool = False,
              tracker=None):
    """One wavefront level: expand all states in ``fr`` into a new frontier.

    Host-loop engine: syncs on ``fr.count`` to size the chunk loop (the
    fused engine in ``core.engine`` keeps this loop on device)."""
    tr = telemetry.get(tracker)
    w = fr.w
    count = int(fr.count)
    tr.count(host_syncs=1)
    # adaptive block: early levels / small instances have tiny frontiers —
    # a fixed 1024-row block pays full padding cost per chunk (§Perf iter).
    # Rounding to powers of two bounds the number of jit signatures at
    # log2(block).
    block = max(32, min(block, _pow2_at_least(max(count, 1))))
    if cap % block:
        # dynamic_slice clamps out-of-range starts, so a non-dividing block
        # would silently re-expand earlier rows with the wrong valid mask
        raise ValueError(f"block ({block}) must divide cap ({cap})")
    out = jnp.zeros((cap, w), dtype=U32)
    ocount = dropped = refills = appended = jnp.asarray(0, dtype=jnp.int32)
    filt = backend_lib.get_op("bloom_make_filter", backend)(
        m_bits if mode == "bloom" else None)
    kdev = jnp.asarray(k, dtype=jnp.int32)

    n_chunks = max(1, -(-count // block))
    for c in range(n_chunks):
        lo = c * block
        states_chunk = jax.lax.dynamic_slice(fr.states, (lo, 0), (block, w))
        chunk_valid = (jnp.arange(block, dtype=jnp.int32) + lo) < fr.count
        out, ocount, dropped, filt, refills, appended = _chunk_step(
            adj_dev, states_chunk, chunk_valid, kdev, out, ocount, dropped,
            filt, allowed_dev, refills, appended, n=n, block=block, mode=mode,
            use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
            schedule=schedule, backend=backend,
            use_simplicial=use_simplicial)
        tr.count(dispatches=1)

    if mode == "sort" and n_chunks > 1:
        out, ocount, drop2 = _final_dedup(out, ocount, cap)
        # cross-chunk duplicates removed; drops before dedup stay counted
        dropped = dropped + drop2
        tr.count(dispatches=1)

    new_fr = frontier_lib.Frontier(out, ocount, dropped)
    generated, dropped_h, refills_h, appended_h = (
        int(x) for x in jax.device_get((ocount, dropped, refills, appended)))
    stats = LevelStats(expanded=count, generated=generated,
                       dropped=dropped_h)
    tr.count(host_syncs=2, refills=refills_h, appended_rows=appended_h,
             lane_levels=1)
    # occupancy vs the planned capacity: how full the frontier buffer
    # actually got (the host loop sees every level, so this is the true
    # per-level peak; compare against the ``frontier_cap`` gauge)
    tr.gauge_max("frontier_peak_rows", stats.generated)
    return new_fr, stats


# ----------------------------------------------------------------- decision

@dataclasses.dataclass
class DecideResult:
    feasible: bool
    inexact: bool
    expanded: int
    levels: Optional[list]    # host snapshots when reconstructing


def decide(g: Graph, k: int, clique: list, *, cap: int, block: int,
           mode: str, use_mmw: bool, m_bits: int, k_hashes: int,
           schedule: str, backend: str = "jax",
           use_simplicial: bool = False, keep_levels: bool = False,
           engine: str = "fused", tracker=None) -> DecideResult:
    """Is tw(g) <= k?  (Monte-Carlo 'no' possible in bloom mode / overflow.)

    ``engine="fused"`` runs the whole level/chunk recursion as one compiled
    program on the device (one dispatch, one sync — §3's design point);
    ``engine="host"`` drives the level loop from the host, which is the
    only engine that can snapshot per-level frontiers (``keep_levels``,
    needed for order reconstruction).  ``backend`` picks the op
    implementations (jax reference vs fused pallas kernels) through the
    registry — validated here, before any tracing starts."""
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits)
    tr = telemetry.get(tracker)
    n = g.n
    target = n - max(k + 1, len(clique))
    if target <= 0:
        return DecideResult(True, False, 0, [] if keep_levels else None)

    w = bitset.n_words(n)
    adj_dev = jnp.asarray(g.packed())
    allowed_dev = jnp.asarray(bitset.np_allowed(n, clique))

    if keep_levels:
        engine = "host"            # per-level snapshots need the host loop
    if engine not in ("host", "fused"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine == "host":
        # fail before any level runs, like the fused engine does — not at
        # the first level whose adapted block happens not to divide cap
        engine_lib.validate_geometry(cap, block, adaptive=True)

    if engine == "fused":
        with tr.time_block("rung_s"):
            feasible, inexact, expanded, _fr = engine_lib.fused_decide(
                adj_dev, allowed_dev, k, target, n=n, cap=cap, block=block,
                mode=mode, use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
                schedule=schedule, backend=backend,
                use_simplicial=use_simplicial, tracker=tr)
        # the fused loop only surfaces the final frontier, so this is a
        # lower bound on the true per-level peak (the host loop's gauge
        # sees every level)
        tr.gauge_max("frontier_peak_rows", int(_fr.count))
        return DecideResult(feasible, inexact, expanded, None)

    fr = frontier_lib.empty_frontier(cap, w)
    expanded = 0
    inexact = False
    levels = [frontier_lib.to_host(fr)] if keep_levels else None

    with tr.time_block("rung_s"):
        for _level in range(target):
            fr, stats = run_level(adj_dev, fr, k, allowed_dev, n=n, cap=cap,
                                  block=block, mode=mode, use_mmw=use_mmw,
                                  m_bits=m_bits, k_hashes=k_hashes,
                                  schedule=schedule, backend=backend,
                                  use_simplicial=use_simplicial, tracker=tr)
            expanded += stats.expanded
            inexact |= stats.dropped > 0
            if keep_levels:
                levels.append(frontier_lib.to_host(fr))
            tr.count(host_syncs=1)
            if int(fr.count) == 0:
                return DecideResult(False, inexact, expanded, levels)
    return DecideResult(True, inexact, expanded, levels)


# ----------------------------------------------------------- reconstruction

def reconstruct_order(g: Graph, k: int, clique: list, levels: list) -> list:
    """Backtrack an elimination order from host level snapshots; numpy only."""
    n = g.n
    adjb = [list(map(bool, row)) for row in g.adj]
    final = levels[-1]
    assert len(final) > 0
    cur = final[0]
    order_rev = []
    for lev in range(len(levels) - 1, 0, -1):
        prev_set = {bytes(row.tobytes()) for row in levels[lev - 1]}
        cur_set = bitset.np_unpack(cur, n)
        found = False
        for v in sorted(cur_set):
            parent = cur.copy()
            parent[v >> 5] &= ~(np.uint32(1) << np.uint32(v & 31))
            if bytes(parent.tobytes()) in prev_set:
                d = expand.degree_oracle(adjb, cur_set - {v}, v)
                if d <= k:
                    order_rev.append(v)
                    cur = parent
                    found = True
                    break
        assert found, "reconstruction failed: no parent in previous level"
    order = list(reversed(order_rev))
    remaining = sorted(set(range(n)) - set(order))
    return order + remaining


def order_width(g: Graph, order: list) -> int:
    """Replay an elimination order; max degree at elimination (oracle)."""
    adj = [set(np.nonzero(g.adj[v])[0]) for v in range(g.n)]
    width = 0
    for v in order:
        width = max(width, len(adj[v]))
        nbrs = list(adj[v])
        for i in range(len(nbrs)):
            for j in range(i + 1, len(nbrs)):
                adj[nbrs[i]].add(nbrs[j])
                adj[nbrs[j]].add(nbrs[i])
        for u in nbrs:
            adj[u].discard(v)
        adj[v].clear()
    return width


# --------------------------------------------------------------- top level

@dataclasses.dataclass
class SolveResult:
    width: int
    exact: bool
    lb: int
    ub: int
    expanded: int
    time_sec: float
    order: Optional[list] = None
    per_k: Optional[dict] = None


@dataclasses.dataclass
class BlockPlan:
    """Everything iterative deepening needs to run one block.

    Shared between ``solve_block`` (sequential and speculative lanes) and
    ``batch.solve_many`` (cross-instance lanes) so the two drivers cannot
    drift in bounds, start-k, or exactness semantics.  ``result`` is set
    when no search is needed (trivial graph, ``lb >= ub``, or a forced
    ``start_k`` at/above ``ub``); its ``time_sec`` is 0 and callers stamp
    their own.
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
        from . import bounds_engine
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


def solve_block(g: Graph, *, cap: Optional[int], block: int, mode: str,
                use_mmw: bool,
                m_bits: int, k_hashes: int, schedule: str, use_clique: bool,
                use_paths: bool, reconstruct: bool, start_k: Optional[int],
                verbose: bool, backend: str = "jax",
                use_simplicial: bool = False,
                engine: str = "fused", lanes: int = 1, shards: int = 1,
                donate_ratio: Optional[float] = None,
                heuristics: int = 0, seed: int = 0,
                tracker=None) -> SolveResult:
    """Iterative deepening on one (biconnected) block.

    ``cap=None`` right-sizes the frontier buffer for this block with
    ``batch.plan_capacity`` (drop-free state bound, clamped to
    ``batch.DEFAULT_CAP``) — bit-identical results, far smaller buffers
    for small blocks.

    ``lanes > 1`` enables speculative deepening: ``decide`` for
    ``k, k+1, ..., k+lanes-1`` runs as one multi-lane dispatch
    (``batch.decide_batch``) and the smallest feasible rung wins.
    Accounting mirrors the sequential ladder exactly — rungs above the
    first feasible one are discarded uncounted — so widths, exactness,
    ``expanded`` and ``per_k`` are bit-identical to ``lanes=1``.
    Speculation needs the fused device loop and no level snapshots;
    with ``engine="host"`` or ``reconstruct=True`` it falls back to
    sequential rungs.

    ``shards > 1`` decides each rung with the frontier split across S
    concurrent workers (``core.shard``: single-writer ownership routing +
    threshold work donation) — bit-identical verdicts/``expanded``/
    ``per_k``, aggregate frontier capacity S× larger.  Sharding takes the
    whole device, so it forces ``lanes=1``; reconstruction replays the
    winning rung on the host engine uncounted (the scheduler's
    ``_certify`` pattern).  ``shards=1`` is exactly the unsharded path
    (no wrapper, no counter drift)."""
    t0 = time.time()
    tr = telemetry.get(tracker)
    plan = plan_block(g, use_clique=use_clique, use_paths=use_paths,
                      start_k=start_k, heuristics=heuristics, seed=seed)
    tr.count(paths_flows=plan.paths_flows, paths_pairs=plan.paths_pairs)
    if plan.result is not None:
        return dataclasses.replace(plan.result, time_sec=time.time() - t0)
    if cap is None:
        from . import batch as batch_lib
        cap = batch_lib.plan_capacity(g.n, block=block)
    # planned capacity for this block — read it against the
    # ``frontier_peak_rows`` high-watermark the engines ratchet
    tr.gauge("frontier_cap", cap)

    shard_n = max(1, int(shards))
    if shard_n > 1 and engine != "fused":
        shard_n = 1       # the host loop is single-frontier only
    spec = max(1, int(lanes))
    if spec > 1 and (reconstruct or engine != "fused" or shard_n > 1):
        spec = 1          # snapshots/host loop/sharding are single-lane only
    decide_kw = dict(cap=cap, block=block, mode=mode, use_mmw=use_mmw,
                     m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
                     backend=backend, use_simplicial=use_simplicial)
    per_k: dict = {}
    expanded_total = 0
    any_inexact = False
    k = plan.k0
    while k < plan.ub:
        ks = list(range(k, min(k + spec, plan.ub)))
        if shard_n > 1:
            from . import shard as shard_lib
            with tr.time_block("rung_s"):
                results = [shard_lib.decide_sharded(
                    plan.graph_at(ks[0]), ks[0], plan.clique,
                    shards=shard_n, donate_ratio=donate_ratio,
                    tracker=tr, **decide_kw)]
        elif spec > 1:
            from . import batch as batch_lib
            with tr.time_block("rung_s"):
                results = batch_lib.decide_batch(
                    g, ks, plan.clique,
                    graphs=[plan.graph_at(kk) for kk in ks],
                    tracker=tr, **decide_kw)
        else:
            results = [decide(plan.graph_at(ks[0]), ks[0], plan.clique,
                              keep_levels=reconstruct, engine=engine,
                              tracker=tr, **decide_kw)]
        for kk, res in zip(ks, results):
            expanded_total += res.expanded
            # per-rung accounting, mirroring ``batch.InstanceState.feed``
            # so a solo solve and a served request report the same
            # rung-level counters
            counts = dict(rungs_decided=1, expanded=res.expanded)
            if res.inexact:
                counts["rung_overflows"] = 1
            tr.count(**counts)
            per_k[kk] = {"feasible": res.feasible, "inexact": res.inexact,
                         "expanded": res.expanded}
            if verbose:
                print(f"  [{g.name}] k={kk} feasible={res.feasible} "
                      f"expanded={res.expanded} inexact={res.inexact}",
                      flush=True)
            if res.feasible:
                order = None
                if reconstruct:
                    levels = getattr(res, "levels", None)
                    if levels is None:
                        # sharded rung: replay the winning k on the host
                        # engine for snapshots, uncounted (the scheduler's
                        # ``_certify`` pattern — expanded stays the ladder's)
                        levels = decide(plan.graph_at(kk), kk, plan.clique,
                                        keep_levels=True, engine="host",
                                        tracker=tr, **decide_kw).levels
                    order = reconstruct_order(plan.graph_at(kk), kk,
                                              plan.clique, levels)
                return SolveResult(kk, plan.exact_at(kk, any_inexact),
                                   plan.lb, plan.ub, expanded_total,
                                   time.time() - t0, order, per_k)
            if res.inexact:
                any_inexact = True
                # a state leading to a width-k order may have been dropped:
                # anything concluded beyond this k is a candidate value only
                # (paper: struck-through entries). We keep going like the
                # paper.
        k = ks[-1] + 1
    return SolveResult(plan.ub, not any_inexact, plan.lb, plan.ub,
                       expanded_total, time.time() - t0, plan.ub_order,
                       per_k)


@dataclasses.dataclass
class SuiteFold:
    """Accumulator folding per-block results into one instance result —
    the single source of ``solve``'s preprocess-path semantics, shared
    with ``batch.solve_many`` so the two drivers cannot drift."""
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


def solve(g: Graph, *, cap: Optional[int] = None, block: int = 1 << 11,
          mode: str = "sort", use_mmw: bool = False, m_bits: int = 1 << 24,
          k_hashes: int = bloom.DEFAULT_K, schedule: Optional[str] = None,
          use_clique: bool = True, use_paths: bool = True,
          use_preprocess: bool = True, reconstruct: bool = False,
          start_k: Optional[int] = None, verbose: bool = False,
          backend: str = "jax", use_simplicial: bool = False,
          engine: str = "fused", lanes: int = 1, shards: int = 1,
          donate_ratio: Optional[float] = None,
          heuristics: int = 0, seed: int = 0,
          impl: Optional[str] = None, tracker=None) -> SolveResult:
    """Compute the treewidth of ``g``.  See module docstring for modes.

    ``cap`` bounds the frontier buffer (rows per level).  The default
    ``cap=None`` auto-sizes it per preprocessed block with
    ``batch.plan_capacity``: the block's drop-free state bound, clamped
    to ``batch.DEFAULT_CAP`` (= the old fixed ``1 << 17`` default) —
    results are bit-identical to the fixed buffer, small blocks just stop
    paying its footprint.  Pass an explicit power of two to pin it.
    ``engine`` selects the wavefront driver: "fused" (device-resident
    ``lax.while_loop``, one dispatch per k) or "host" (per-level host loop;
    forced automatically where reconstruction needs level snapshots).
    ``backend`` selects the op implementations through the registry
    (``repro.core.backend``; the ad-hoc ``impl=`` string it replaced
    survives only as a deprecated alias of this knob): "jax" reference or
    fused "pallas" kernels.
    ``schedule=None`` resolves to the backend's default closure fixpoint
    ("while" for jax, the static "doubling" baked into the pallas kernels).
    ``lanes > 1`` turns the deepening ladder speculative: each dispatch
    decides ``lanes`` consecutive k concurrently through the multi-lane
    engine (``core.batch``) — same results, fewer dispatches.
    ``shards > 1`` splits each rung's *frontier* across S concurrent
    workers instead (``core.shard``: single-writer ownership routing,
    threshold work donation tuned by ``donate_ratio``) — bit-identical
    results with S× the aggregate frontier capacity; forces ``lanes=1``.
    ``heuristics > 0`` runs that many anytime bounds-improver rounds
    (``core.bounds_engine``) before each block's ladder: an improved lb
    skips already-refuted rungs, an improved ub clamps the ladder with an
    order certificate — the reported width/exactness never change, only
    the number of exact rungs paid for them.  ``seed`` pins every
    heuristic for bit-reproducible plans.
    ``reconstruct=True`` returns a certified elimination order; with
    preprocessing on, each block is reconstructed with the host engine and
    the block-local orders are stitched back through the preprocess vertex
    maps (``preprocess.stitch_block_orders``).  To batch *across*
    instances, see ``batch.solve_many``; to serve a concurrent request
    stream, see ``repro.serve.twscheduler``."""
    t0 = time.time()
    if impl is not None:
        warnings.warn("solve(impl=...) is deprecated; use backend=...",
                      DeprecationWarning, stacklevel=2)
        backend = impl
    if schedule is None:
        schedule = "doubling" if backend == "pallas" else "while"
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits, lanes=int(lanes),
                         shards=int(shards))
    if g.n == 0:
        return SolveResult(0, True, 0, 0, 0, 0.0, [], {})
    solve_kw = dict(cap=cap, block=block, mode=mode, use_mmw=use_mmw,
                    m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
                    use_clique=use_clique, use_paths=use_paths,
                    start_k=start_k, verbose=verbose, backend=backend,
                    use_simplicial=use_simplicial, engine=engine,
                    lanes=lanes, shards=shards, donate_ratio=donate_ratio,
                    heuristics=heuristics, seed=seed, tracker=tracker)
    if not use_preprocess:
        return solve_block(g, reconstruct=reconstruct, **solve_kw)

    pre = preprocess_lib.preprocess(g)
    fold = SuiteFold.start(pre.lb)
    block_orders: list = [None] * len(pre.blocks)
    for i, part in enumerate(pre.blocks):
        if fold.skip(part.g):
            continue
        res = solve_block(part.g, reconstruct=reconstruct, **solve_kw)
        fold.add(part.g.name, res)
        block_orders[i] = res.order
    order = None
    if reconstruct:
        order = stitch_and_verify(g, pre, block_orders, fold.width)
    return fold.result(time.time() - t0, order)


def stitch_and_verify(g: Graph, pre, block_orders: list,
                      width: int) -> Optional[list]:
    """Stitch per-block elimination orders into a global certificate and
    replay-check it (shared by ``solve`` and the lane drivers in
    ``core.batch`` / ``repro.serve.twscheduler`` so their reconstruction
    semantics cannot drift).  Returns ``None`` (with a warning) if the
    stitched order replays above the computed width."""
    order = preprocess_lib.stitch_block_orders(pre, block_orders)
    replay = order_width(g, order)
    if replay > width:
        warnings.warn(
            f"stitched elimination order replays at width {replay} > "
            f"computed width {width}; dropping the order (please "
            "report — this indicates a preprocess/stitch bug)",
            stacklevel=2)
        return None
    return order
