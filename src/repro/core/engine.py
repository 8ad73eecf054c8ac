"""Device-resident wavefront engine: the whole ``decide`` loop in one jit.

The paper's speedup (§3, Listing 1) comes from never letting the Held-Karp
frontier leave the GPU; the host only learns the final verdict.  The
original ``solver.decide`` instead synchronised twice per level (reading
``fr.count`` to size the chunk loop and to test emptiness), serialising
kernel dispatch exactly the way the persistent-worklist literature warns
against.  This module fuses both loops:

  * the per-level loop becomes an outer ``lax.while_loop`` whose carry is
    the ``Frontier`` pytree plus (level, expanded, dropped) counters, with
    the paper's empty-frontier early exit as part of the loop condition;
  * the per-chunk loop becomes an inner ``lax.while_loop`` over fixed-shape
    ``block``-row slices of the frontier buffer, with the trip count bound
    by the *device-resident* count (no host round-trip, no wasted chunks);
  * expansion, simplicial collapse, MMW pruning, sort/Bloom dedup and
    overflow accounting all happen inside the loop body via
    ``expand_chunk`` — the single shared implementation of the paper's
    Listing-1 inner loop, also used by the host-loop path and the
    distributed solver.  Every op inside it resolves through the backend
    registry (``core.backend``): ``backend="jax"`` composes the reference
    implementations, ``backend="pallas"`` dispatches the fused wavefront
    kernel that runs the whole expand→prune pipeline in one VMEM pass.

Every level body runs under ``jax.named_scope("tw.level")``, and inside
it the chunk's expansion, dedup and append under ``tw.expand``,
``tw.dedup`` and ``tw.append``, and a mid-level refill under
``tw.refill``: the scopes land in each HLO op's ``op_name`` metadata, so
a profiler trace says which part of the level a device op (or the fusion
it ended up in) belongs to (DESIGN.md §14).

``decide_loop`` is compiled in one place, under the lane ``vmap`` of
``core.batch``: a single-device rung, one lane or eight, is one dispatch
and one device→host transfer of counts, versus O(levels × chunks) for the
host reference loop (``solver.run_level``), which survives for the
per-level snapshots that reconstruction needs and as the tests'
reference.  The sharded and mesh programs (``core.shard``,
``core.distributed``) run their own level loops over ``chunk_sweep``.

``COUNTERS`` is a deprecated read-only view of the root tracker's
dispatch and host-sync counts (``core.telemetry``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import backend as backend_lib
from . import dedup
from . import frontier as frontier_lib
from . import telemetry

U32 = jnp.uint32

# dispatch/sync accounting (host-side, zero overhead on device):
#   dispatches — jitted program launches issued by a solver path
#   host_syncs — device->host scalar/buffer reads that block on the device
# plus shard-health counters fed by the sharded engine (core.shard /
# core.distributed): donation events/rows, idle-shard level steps, and the
# peak per-shard occupancy seen (a max, not a sum).
#
# Accounting lives in ``core.telemetry`` now (thread-safe, scoped,
# pluggable sinks — DESIGN.md §14); ``COUNTERS`` survives as a deprecated
# read-only view over the root tracker so historical asserts keep working.
COUNTERS = telemetry.COUNTERS


def reset_counters():
    """Deprecated: zero the process-root tracker (``telemetry.reset``)."""
    telemetry.reset()


def count(dispatches: int = 0, host_syncs: int = 0, **extra: int):
    """Deprecated shim: count on the process-root tracker.  Library code
    now threads an explicit ``tracker=`` instead."""
    kw = dict(extra)
    if dispatches:
        kw["dispatches"] = dispatches
    if host_syncs:
        kw["host_syncs"] = host_syncs
    telemetry.root().count(**kw)


@dataclasses.dataclass
class DispatchHandle:
    """An issued device program whose host sync is deferred.

    JAX dispatches asynchronously: the jitted call returns device arrays
    immediately while the device keeps computing, and the host only
    blocks when it *reads* them.  The engine entry points exploit that by
    splitting every decide into launch (enqueue the program, hold the
    result arrays) and ``result()`` (the single deferred ``device_get``):
    between the two, the caller owns the host — the async solve service
    (``repro.serve.twscheduler``) runs admission and planning for the
    *next* dispatch there, overlapping host bookkeeping with device work.

    ``result()`` performs the one host sync (counted in ``COUNTERS``),
    converts through ``finalize``, and caches — calling it again is free.
    ``ready()`` is a non-blocking poll of the underlying arrays.

        h = batch.decide_lanes_async(lanes, block=block, mode=mode, ...)
        ...                       # host free while the device works
        verdicts = h.result()     # the only sync
    """
    arrays: Any                     # pytree of in-flight device arrays
    finalize: Callable[[Any], Any]  # host values -> caller-shaped result
    tracker: Any = None             # telemetry scope (None = process root)
    _result: Any = None
    _done: bool = False
    _t0: float = dataclasses.field(default_factory=time.perf_counter)

    def ready(self) -> bool:
        """Has the device finished?  Never blocks (best-effort: arrays
        without an ``is_ready`` probe report True)."""
        return all(getattr(a, "is_ready", lambda: True)()
                   for a in jax.tree_util.tree_leaves(self.arrays))

    def result(self):
        """Block for the verdict: one host sync, then cached.  The sync
        and the launch→result wall-clock land on the handle's tracker."""
        if not self._done:
            tr = telemetry.get(self.tracker)
            with tr.span("tw.wait"):
                host = jax.device_get(self.arrays)
            tr.count(host_syncs=1)
            tr.timing("dispatch_wall_s", time.perf_counter() - self._t0)
            self._result = self.finalize(host)
            self.arrays = None       # release the device references
            self._done = True
        return self._result

    def discard(self) -> None:
        """Abandon the dispatch without ever reading it: release the
        device references and mark the handle done with no result.  The
        program still runs to completion on device (a launched XLA
        program cannot be aborted), but the host never blocks on it and
        no ``host_syncs`` is counted — the traffic-shaping scheduler uses
        this for whole-round abandonment (``recover``) and cancelled
        requests whose verdicts nobody will read.  After ``discard``,
        ``result()`` returns ``None``."""
        if not self._done:
            self.arrays = None
            self._result = None
            self._done = True


def validate_geometry(cap: int, block: int, *, adaptive: bool = False) -> int:
    """Fail fast on buffer geometry the chunk slicer cannot walk cleanly.

    ``dynamic_slice`` clamps out-of-range starts, so a block that does not
    divide the buffer capacity would silently re-expand earlier rows under
    a wrong valid mask.  ``adaptive=True`` checks every block size the host
    loop's per-level adaptation (``max(32, min(block, 2^j))``) can pick.
    Returns the (possibly clamped) block.
    """
    block = min(block, cap)
    sizes = ({max(32, min(block, 1 << j)) for j in range(26)}
             if adaptive else {block})
    bad = sorted(b for b in sizes if cap % b)
    if bad:
        raise ValueError(
            f"block ({bad[0]}{' via adaptive sizing' if adaptive else ''}) "
            f"must divide cap ({cap}): the chunk slicer walks the buffer "
            "in block strides. Use a power-of-two cap >= block")
    return block


# ------------------------------------------------------------- chunk kernel

def expand_chunk(adj, states_chunk, chunk_valid, k, out, ocount, dropped,
                 filt, allowed, *, n, block, mode, use_mmw, m_bits,
                 k_hashes, schedule, backend, use_simplicial=False):
    """Expand one chunk of states and append deduped children to ``out``.

    The paper's Listing-1 inner loop in one place: called from the host
    chunk loop (``solver._chunk_step``), from the fused while_loop below,
    and from the distributed per-device expansion.  Pure function of its
    arguments — safe inside any jit / while_loop / shard_map context.

    Every op dispatches through the backend registry: under
    ``backend="pallas"`` the whole expand → feasibility → prune pipeline
    runs as one fused VMEM-resident kernel emitting (children, feasible)
    directly; under ``backend="jax"`` the same pipeline is composed from
    the reference implementations in ``core/*``.
    """
    w = adj.shape[-1]
    with jax.named_scope("tw.expand"):
        children, feas = backend_lib.get_op("wavefront_expand", backend)(
            adj, states_chunk, chunk_valid, k, allowed, n=n,
            schedule=schedule, use_mmw=use_mmw,
            use_simplicial=use_simplicial)
        flat = children.reshape(block * n, w)
        fmask = feas.reshape(block * n)

    with jax.named_scope("tw.dedup"):
        # intra-chunk exact dedup (paper: mutex-striped atomic inserts)
        skeys, keep = backend_lib.get_op("sort_dedup", backend)(flat, fmask)
        if mode == "bloom":
            keep, filt = backend_lib.get_op("bloom_query_insert", backend)(
                filt, skeys, keep, m_bits=m_bits, k_hashes=k_hashes)

    with jax.named_scope("tw.append"):
        out, written, drop = dedup.compact(skeys, keep, out, ocount)
        dropped = dropped + drop
        ocount = ocount + written
    return out, ocount, dropped, filt


def refill_needed(ocount, live_rows, allowed, cap: int):
    """Could the children of a chunk's ``live_rows`` states overflow a
    buffer that holds ``ocount`` rows?  A state has at most one child per
    candidate vertex (``allowed``).  A refill of an empty buffer would
    change nothing."""
    cands = jnp.sum(jax.lax.population_count(allowed)).astype(jnp.int32)
    return (ocount > 0) & (ocount + live_rows * cands > cap)


def any_lane(flag, lane_axis=None):
    """``flag`` of some lane of the ``vmap`` named ``lane_axis``: one value
    for every lane (``flag`` itself without a lane axis)."""
    if lane_axis is None:
        return flag
    return jax.lax.pmax(flag.astype(jnp.int32), lane_axis) > 0


def refill(out, ocount, need, *, lane_axis=None):
    """Sort-dedup and compact the ``ocount`` rows of ``out`` in place,
    when ``need``: the mid-level refill that keeps a level's append
    stream within one buffer (DESIGN.md §2).  Returns (out, ocount,
    refilled).

    Drop-neutral (the distinct rows of a buffer fit in it), and it only
    reorders a set that the level's cross-chunk dedup sorts anyway, so a
    refill leaves the level's frontier as it was.  Under the lane
    ``vmap`` (``lane_axis`` names its axis) the predicate is the max over
    the lanes: a ``cond`` on a per-lane value would become a ``select``
    that sorts every lane's whole buffer on every chunk, while a
    lane-uniform one stays a conditional taken only when some lane
    needs it."""
    need = any_lane(need, lane_axis)

    def _refill():
        with jax.named_scope("tw.refill"):
            cap = out.shape[0]
            valid = jnp.arange(cap, dtype=jnp.int32) < ocount
            buf, count, _ = dedup.dedup_compact(out, valid, cap)
            return buf, count

    out, ocount = jax.lax.cond(need, _refill, lambda: (out, ocount))
    return out, ocount, need.astype(jnp.int32)


# ------------------------------------------------------------- fused level

# below this frontier size a level runs as one narrow chunk instead of a
# full-``block``-wide one — the device analogue of the host loop's adaptive
# block (early levels have tiny frontiers; a fixed wide block pays full
# padding cost per level)
SMALL_BLOCK = 128


def chunk_sweep(adj, allowed, k, states, count_, blk, *, n, cap, mode,
                use_mmw, m_bits, k_hashes, schedule, backend,
                use_simplicial, max_chunks=None, cross_dedup=True,
                lane_axis=None):
    """Expand ``count_`` rows of ``states`` in ``blk``-row chunks, on device.

    The data-dependent chunk loop shared by the fused level step and the
    distributed per-device expansion (which passes ``cross_dedup=False`` —
    its cross-chunk dedup happens at the owner after routing — and a
    ``max_chunks`` bound from its local capacity).  Returns
    (out, ocount, dropped, refills, appended): the last two count the
    mid-level refills and the rows the chunks appended.

    With exact cross-chunk dedup (sort mode, ``cross_dedup``), a chunk
    whose children could overflow the buffer is preceded by a
    ``refill``, so the buffer holds the level's distinct rows plus one
    chunk's children rather than its whole append stream.  ``lane_axis``
    names the lane ``vmap``'s axis: the chunk loop stops for every lane
    before a chunk that could overflow some lane's buffer, and the
    refill, lane-uniform, runs between chunk loops.

    Lane-aware by construction: nothing here reads the true vertex count —
    ``n`` only sizes the (static) candidate axis, while which vertices
    exist rides in ``allowed`` and which rows are live rides in ``count_``.
    The multi-lane engine exploits that by padding every lane to a common
    ``n`` and vmapping the caller (``core.batch``); the chunk while_loop
    then trips ``max_l ceil(count_l / blk)`` times with finished lanes'
    carries frozen per the while_loop batching rule."""
    w = adj.shape[-1]
    zero = jnp.asarray(0, jnp.int32)
    out = jnp.zeros((cap, w), dtype=U32)
    filt = backend_lib.get_op("bloom_make_filter", backend)(
        m_bits if mode == "bloom" else None)
    exact_cross = mode == "sort" and cross_dedup

    def live(c):
        more = c[0] * blk < count_
        if max_chunks is not None:
            more = more & (c[0] < max_chunks)
        return more

    def chunk_body(c):
        ci, out, ocount, dropped, filt, refills, appended, _fresh = c
        lo = ci * blk
        states_chunk = jax.lax.dynamic_slice(states, (lo, zero), (blk, w))
        chunk_valid = (jnp.arange(blk, dtype=jnp.int32) + lo) < count_
        before = ocount
        out, ocount, dropped, filt = expand_chunk(
            adj, states_chunk, chunk_valid, k, out, ocount, dropped, filt,
            allowed, n=n, block=blk, mode=mode, use_mmw=use_mmw,
            m_bits=m_bits, k_hashes=k_hashes, schedule=schedule,
            backend=backend, use_simplicial=use_simplicial)
        return (ci + 1, out, ocount, dropped, filt, refills,
                appended + ocount - before, jnp.asarray(False))

    carry = (zero, out, zero, zero, filt, zero, zero, jnp.asarray(False))
    if not exact_cross:
        carry = jax.lax.while_loop(live, chunk_body, carry)
    else:
        # The chunk loop stops before a chunk that could overflow some
        # lane's buffer, and the refill runs between chunk loops: a
        # conditional inside the chunk loop would keep the loop's buffers
        # out of the chip's fast memory.  ``fresh`` lets a just-refilled
        # buffer take its next chunk even if it could still overflow.
        def needs_refill(c):
            rows = jnp.clip(count_ - c[0] * blk, 0, blk)
            return live(c) & refill_needed(c[2], rows, allowed, cap)

        def chunk_cond(c):
            return live(c) & (c[7] | ~any_lane(needs_refill(c), lane_axis))

        def segment(c):
            c = jax.lax.while_loop(chunk_cond, chunk_body, c)
            ci, out, ocount, dropped, filt, refills, appended, _ = c
            out, ocount, did = refill(out, ocount, needs_refill(c),
                                      lane_axis=lane_axis)
            return (ci, out, ocount, dropped, filt, refills + did, appended,
                    did > 0)

        carry = jax.lax.while_loop(live, segment, carry)
    _, out, ocount, dropped, _, refills, appended, _ = carry

    if exact_cross:
        # cross-chunk exact dedup, only when the level actually spanned
        # multiple chunks (single-chunk output is already sorted-unique);
        # the full-``cap`` sort is the priciest op in the level, so the
        # gate matters.  Drop-neutral: n_keep <= ocount <= cap, drop2 == 0.
        def _cross_dedup():
            with jax.named_scope("tw.dedup"):
                valid = jnp.arange(cap, dtype=jnp.int32) < ocount
                buf, written, drop2 = dedup.dedup_compact(out, valid, cap)
                return buf, written, dropped + drop2

        out, ocount, dropped = jax.lax.cond(
            count_ > blk, _cross_dedup, lambda: (out, ocount, dropped))
    return out, ocount, dropped, refills, appended


def _level_step(adj, allowed, k, fr, *, n, cap, block, mode, use_mmw,
                m_bits, k_hashes, schedule, backend, use_simplicial,
                lane_axis=None, active=True):
    """One wavefront level, fully on device.  Traced inside the while body.

    Chunk trip count is ``ceil(count / block)`` with the count read from the
    carried frontier — a data-dependent while_loop, so small frontiers pay
    for one chunk, not ``cap / block``.  Levels whose whole frontier fits in
    ``SMALL_BLOCK`` rows take a narrow single-chunk branch instead
    (``lax.cond`` — both branches compiled once, runtime picks per level).
    Returns (frontier, refills, appended).

    Under the lane ``vmap`` the ``cond`` is a ``select`` that runs both
    sweeps, and a finished lane (``active`` false) still runs the level
    body: each sweep is therefore given only the rows of the lanes that
    take it, so the narrow sweep walks at most one chunk and a finished
    lane none.  The results it discards are all it changes.
    """
    small = min(block, SMALL_BLOCK)
    count_ = jnp.where(active, fr.count, 0)
    kwargs = dict(n=n, cap=cap, mode=mode, use_mmw=use_mmw, m_bits=m_bits,
                  k_hashes=k_hashes, schedule=schedule, backend=backend,
                  use_simplicial=use_simplicial, lane_axis=lane_axis)

    if small == block:
        out, ocount, dropped, refills, appended = chunk_sweep(
            adj, allowed, k, fr.states, count_, block, **kwargs)
    else:
        narrow = count_ <= small
        out, ocount, dropped, refills, appended = jax.lax.cond(
            narrow,
            lambda: chunk_sweep(adj, allowed, k, fr.states,
                                jnp.where(narrow, count_, 0), small,
                                **kwargs),
            lambda: chunk_sweep(adj, allowed, k, fr.states,
                                jnp.where(narrow, 0, count_), block,
                                **kwargs))

    return (frontier_lib.Frontier(out, ocount.astype(jnp.int32),
                                  dropped.astype(jnp.int32)),
            refills, appended)


def decide_loop(adj, allowed, k, target, fr, *, n, cap, block, mode,
                use_mmw, m_bits, k_hashes, schedule, backend,
                use_simplicial, lane_axis=None):
    """Run up to ``target`` wavefront levels; stop early on emptiness.

    Returns (frontier, levels_run, expanded, dropped_total, refills,
    appended) — all on device.  Feasibility is ``frontier.count > 0``
    (the loop only stops short of ``target`` when a level produced no
    states); ``refills`` and ``appended`` count the mid-level refills and
    the rows the levels appended (``chunk_sweep``).

    Undecorated on purpose: its one compiled user is the lane engine
    (``batch._lanes_decide``), which vmaps it over a leading lane axis,
    named by ``lane_axis``, for one lane or many.  Under vmap the two
    data-dependent ``while_loop``s become masked loops — a lane whose
    condition goes false has its carry frozen by the batching rule's
    ``select`` while other lanes keep stepping, which is exactly the
    per-lane early exit the batched engine needs (and why batched results
    stay bit-identical per lane).  ``n`` is the (static) padded lane
    width; a lane's true vertex count is carried dynamically by its
    ``allowed`` mask and ``target``.
    """
    zero = jnp.asarray(0, jnp.int32)

    def cond(carry):
        fr, level = carry[:2]
        return (level < target) & (fr.count > 0)

    def body(carry):
        with jax.named_scope("tw.level"):
            fr, level, expanded, dropped, refills, appended = carry
            expanded = expanded + fr.count
            new_fr, r, a = _level_step(
                adj, allowed, k, fr, n=n, cap=cap, block=block, mode=mode,
                use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
                schedule=schedule, backend=backend,
                use_simplicial=use_simplicial, lane_axis=lane_axis,
                active=cond(carry))
            return (new_fr, level + 1, expanded, dropped + new_fr.dropped,
                    refills + r, appended + a)

    return jax.lax.while_loop(cond, body, (fr,) + (zero,) * 5)
