"""Lane dispatch: one compiled program decides B rungs at once.

The single-device decide program of the repository.  ``decide_loop``
(``core.engine``) keeps a whole rung on device; this module runs it under
a leading lane axis, so one dispatch decides B independent subproblems
(component-aware parallel branching in the GPU-vertex-cover sense: run
independent subproblems concurrently until each saturates the device):

  * ``_lanes_decide`` vmaps ``engine.decide_loop`` over a leading lane
    axis.  Each lane carries its own padded ``(adj, allowed, k, target)``
    and ``Frontier`` slice; the while_loop batching rule folds per-lane
    early exit into the masked loop condition (a finished lane's carry is
    frozen by ``select`` while the others keep stepping), so every lane's
    result is bit-identical to running it alone.  One lane is the
    single-device rung of ``solver.decide``.
  * ``decide_lanes_async`` is the host entry: pad, pack, one dispatch,
    and a handle whose one host sync copies counts, never a frontier;
    ``decide_lanes`` is its blocking form.
  * ``plan_capacity`` — the memory model: right-sizes per-lane frontier
    buffers from the block's state space, the chunk geometry and an
    optional device-memory budget instead of the fixed worst-case ``cap``
    (DESIGN.md §10).

The deepening ladder that decides which rungs to pack (``core.ladder``)
and the serve scheduler (``repro.serve.twscheduler``) sit above this
module; nothing here knows about ladders, blocks or requests.

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
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import backend as backend_lib
from . import bitset
from . import engine as engine_lib
from . import frontier as frontier_lib
from . import telemetry
from .graph import Graph

U32 = jnp.uint32

# default lane width of one dispatch: enough to cover a suite round or a
# deepening ladder without blowing the frontier-buffer footprint
# (B * cap * W words resident per dispatch)
DEFAULT_MAX_LANES = 8

# the historical fixed frontier capacity (the solver's old default).
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
    cap = min(pow2_at_least(need), cap_hi)    # round DOWN, never past it
    cap = max(cap, 32, pow2_at_least(min(block, cap_hi)))
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


def pow2_at_least(x: int) -> int:
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
