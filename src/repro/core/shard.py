"""Intra-request scale-out: one instance's frontier across S shards.

One decide rung (is tw(g) <= k?) normally runs on a single frontier
buffer.  This module splits that frontier across ``S`` shards so S
workers — vmapped lanes on one device, or devices in a mesh — decide one
rung concurrently (DESIGN.md §13):

  * **Expansion** is embarrassingly parallel: each shard runs the shared
    ``engine.chunk_sweep`` over its own rows (intra-chunk dedup only).
  * **Dedup** uses single-writer ownership routing (DESIGN.md §2): every
    candidate state is hash-partitioned (murmur3 mod S) to a unique owner
    shard which performs the exact sorted dedup — the jax analogue of the
    paper's mutex-striped Bloom inserts, with nothing to synchronise.
    Under ``mode="bloom"`` each owner additionally guards its rows with
    its *own* Bloom filter shard: one writer per filter, so inserts race
    with nobody (Monte-Carlo FP drops only, exactly the paper's
    semantics).
  * **Donation** rebalances per-rung load: when post-dedup shard
    occupancy skews past ``donate_ratio`` × the mean, overloaded shards
    donate frontier rows to underloaded ones (the worklist-donation
    pattern of the GPU vertex-cover solvers, arxiv 2204.10402) via a
    water-filling repack.  Only already-owned *parent* rows move;
    ownership of any future child is a pure function of the child's
    hash, so donation can never duplicate or lose a state.

Because the union of the per-shard post-dedup frontiers equals the
single-lane post-dedup frontier level by level (sort mode, no
overflow), the sharded verdict, ``expanded`` count and deepening ladder
are bit-identical to one lane of the lane program — see
``tests/test_shard.py``.  Per-shard capacity equals the single-lane
planned capacity, so a drop-free single-lane plan stays drop-free
sharded (each shard's chunk stream and each owner's receive set are
subsets of what the single-lane buffer provably holds).

The mesh path (``mesh=``) delegates to ``core.distributed``, whose
``route_states`` / ``donation_plan`` helpers this module shares — the
distributed solver and the serving pool are one engine path.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import backend as backend_lib
from . import batch as batch_lib
from . import bitset, bloom, dedup
from . import distributed as dist_lib
from . import engine as engine_lib
from . import frontier as frontier_lib
from . import telemetry
from .distributed import (DEFAULT_DONATE_RATIO, donation_plan, record_stats,
                          route_states)
from .graph import Graph

U32 = jnp.uint32

# ---------------------------------------------------------------- donation

def _repack(states: jnp.ndarray, counts: jnp.ndarray,
            targets: jnp.ndarray) -> jnp.ndarray:
    """Redistribute rows so shard d holds ``targets[d]`` rows.

    The single-device donation move: concatenate every shard's live rows
    (in shard order) and re-split at the target boundaries — one gather +
    one scatter, no host participation.  ``sum(targets) == sum(counts)``
    and ``targets <= cap`` (targets are ~total/S, total <= S*cap), so the
    repack is lossless.
    """
    s, cap, w = states.shape
    flat = states.reshape(s * cap, w)
    valid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
             < counts[:, None]).reshape(-1)
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    bounds = jnp.cumsum(targets)
    shard_of = jnp.searchsorted(bounds, rank, side="right") \
        .astype(jnp.int32)
    starts = bounds - targets
    dest = shard_of * cap + (rank - starts[jnp.minimum(shard_of, s - 1)])
    dest = jnp.where(valid & (shard_of < s), dest, s * cap)
    out = jnp.zeros((s * cap, w), dtype=U32).at[dest].set(flat, mode="drop")
    return out.reshape(s, cap, w)


# ----------------------------------------------------------- sharded decide

def sharded_decide_loop(adj, allowed, k, target, fr, *, shards, n, cap,
                        block, mode, use_mmw, m_bits, k_hashes, schedule,
                        backend, use_simplicial, donate_ratio):
    """Run up to ``target`` levels with the frontier split across shards.

    The sharded mirror of ``engine.decide_loop``: same ladder semantics
    (stop early on emptiness, ``expanded`` accumulates the pre-expansion
    frontier size), with each level as

        vmapped local expand  →  ownership route  →  vmapped owner dedup
        →  (Bloom shard probe)  →  threshold donation

    Returns (counts (S,), levels, expanded, dropped, stats) with
    ``stats = [donation_events, donated_rows, idle_shard_steps,
    peak_shard_occupancy]`` (device ints; surfaced through
    ``engine.COUNTERS`` by the dispatch wrappers below).
    """
    s = shards
    w = adj.shape[-1]
    zero = jnp.asarray(0, jnp.int32)
    max_chunks = -(-cap // block)
    lane_idx = jnp.arange(cap, dtype=jnp.int32)

    make_filter = backend_lib.get_op("bloom_make_filter", backend)
    filt0 = make_filter(m_bits if mode == "bloom" else None)
    filts = jnp.stack([filt0] * s)

    def local(st, c):
        # per-shard expansion: intra-chunk dedup only — cross-shard (and
        # cross-chunk) dedup happens at the owner after routing
        return engine_lib.chunk_sweep(
            adj, allowed, k, st, c, block, n=n, cap=cap, mode="sort",
            use_mmw=use_mmw, m_bits=1, k_hashes=1, schedule=schedule,
            backend=backend, use_simplicial=use_simplicial,
            max_chunks=max_chunks, cross_dedup=False)[:3]

    def owner_dedup(rows, rvalid):
        return dedup.dedup_compact(rows, rvalid, cap)

    if mode == "bloom":
        query_insert = backend_lib.get_op("bloom_query_insert", backend)

        def bloom_probe(filt, rows, keep):
            # single writer: only this shard ever inserts into this
            # filter shard, and only rows it owns are probed against it
            keep, filt = query_insert(filt, rows, keep, m_bits=m_bits,
                                      k_hashes=k_hashes)
            buf, written, _ = dedup.compact(
                rows, keep, jnp.zeros((cap, w), dtype=U32))
            return buf, written, filt

    def cond(c):
        _st, counts, _f, level, _e, _d, _stats = c
        return (level < target) & (jnp.sum(counts) > 0)

    def body(c):
        states, counts, filts, level, expanded, dropped, stats = c
        total = jnp.sum(counts)
        expanded = expanded + total
        idle = jnp.sum((counts == 0).astype(jnp.int32))
        peak = jnp.maximum(stats[3], jnp.max(counts))

        out, ocnt, drop_local = jax.vmap(local)(states, counts)
        rows = out.reshape(s * cap, w)
        valid = (lane_idx[None, :] < ocnt[:, None]).reshape(-1)
        recv, rcounts, drop_route = route_states(rows, valid, s, cap)
        rvalid = lane_idx[None, :] < rcounts[:, None]
        buf, cnts, drop_own = jax.vmap(owner_dedup)(recv, rvalid)
        if mode == "bloom":
            bvalid = lane_idx[None, :] < cnts[:, None]
            buf, cnts, filts = jax.vmap(bloom_probe)(filts, buf, bvalid)

        targets, trig, moved = donation_plan(cnts, donate_ratio)
        buf, cnts = jax.lax.cond(
            trig,
            lambda b, c_: (_repack(b, c_, targets), targets),
            lambda b, c_: (b, c_), buf, cnts)

        stats = jnp.stack([
            stats[0] + trig.astype(jnp.int32),
            stats[1] + jnp.where(trig, moved, 0),
            stats[2] + idle,
            peak,
        ])
        dropped = dropped + jnp.sum(drop_local) + drop_route \
            + jnp.sum(drop_own)
        return (buf, cnts, filts, level + 1, expanded, dropped, stats)

    init = (fr.states, fr.count, filts, zero, zero, zero,
            jnp.zeros((4,), jnp.int32))
    _st, counts, _f, level, expanded, dropped, stats = jax.lax.while_loop(
        cond, body, init)
    return counts, level, expanded, dropped, stats


_sharded_decide = functools.partial(
    jax.jit,
    static_argnames=("shards", "n", "cap", "block", "mode", "use_mmw",
                     "m_bits", "k_hashes", "schedule", "backend",
                     "use_simplicial", "donate_ratio"))(sharded_decide_loop)


# ------------------------------------------------------------ host wrappers

def decide_sharded_async(g: Graph, k: int, clique=(), *, shards: int,
                         mesh=None, cap: Optional[int] = None,
                         block: int = 1 << 11, mode: str = "sort",
                         use_mmw: bool = False, m_bits: int = 1 << 24,
                         k_hashes: int = bloom.DEFAULT_K,
                         schedule: Optional[str] = None,
                         backend: str = "jax",
                         use_simplicial: bool = False,
                         donate_ratio: Optional[float] = None,
                         n_pad: Optional[int] = None,
                         budget_bytes: Optional[int] = None,
                         tracker=None) -> engine_lib.DispatchHandle:
    """Enqueue one sharded decide rung; return its ``DispatchHandle``.

    ``handle.result()`` yields a one-element list holding a
    ``batch.LaneResult`` — the same shape one lane of the serving pool
    produces, so a sharded rung slots into ``InstanceState.feed`` and the
    scheduler sync loop unchanged.  ``cap`` is the *per-shard* frontier
    capacity; ``cap=None`` plans the same drop-free bound the single-lane
    path would use, which keeps sharded results bit-identical (aggregate
    headroom only grows with S).  ``n_pad`` embeds the graph in a larger
    static vertex space (the multi-lane padding trick — same caveats as
    DESIGN.md §8).  With ``mesh`` spanning >1 devices the rung runs on
    the mesh via ``core.distributed`` instead of vmapped shards.
    """
    shards = int(shards)
    if schedule is None:
        schedule = backend_lib.default_schedule(backend)
    backend_lib.validate(backend, mode=mode, schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial,
                         m_bits=m_bits, shards=shards)
    ratio = DEFAULT_DONATE_RATIO if donate_ratio is None \
        else float(donate_ratio)

    n = g.n
    target = n - max(k + 1, len(clique))
    if target <= 0:
        res = [batch_lib.LaneResult(True, False, 0)]
        return engine_lib.DispatchHandle((), lambda host: res,
                                         _result=res, _done=True)

    if mesh is not None and getattr(mesh, "devices", None) is not None \
            and mesh.devices.size > 1:
        if mode != "sort":
            raise backend_lib.BackendCapabilityError(
                "mesh-sharded decide performs exact owner dedup only "
                "(mode='sort'); the Bloom filter shards exist on the "
                "single-device sharded engine")
        if cap is None:
            cap = batch_lib.plan_capacity(n, block=block,
                                          budget_bytes=budget_bytes)
        return dist_lib.decide_launch(
            g, k, clique, mesh, cap_local=cap, block=block,
            use_mmw=use_mmw, use_simplicial=use_simplicial,
            schedule=schedule, backend=backend, donate_ratio=ratio,
            tracker=tracker)

    n_static = n if n_pad is None else int(n_pad)
    if n_static < n:
        raise ValueError(f"n_pad={n_pad} below instance size {n}")
    w = bitset.n_words(n_static)
    if cap is None:
        cap = batch_lib.plan_capacity(n, w, lanes=shards, block=block,
                                      budget_bytes=budget_bytes)
    block = engine_lib.validate_geometry(cap, block)

    adj = np.zeros((n_static, w), dtype=np.uint32)
    p = g.packed()
    adj[:n, :p.shape[1]] = p
    allowed = bitset.np_allowed(n, clique, w)
    fr = frontier_lib.shard_frontiers(shards, cap, w)

    counts, _level, expanded, dropped, stats = _sharded_decide(
        jnp.asarray(adj), jnp.asarray(allowed),
        jnp.asarray(k, jnp.int32), jnp.asarray(target, jnp.int32), fr,
        shards=shards, n=n_static, cap=cap, block=block, mode=mode,
        use_mmw=use_mmw, m_bits=m_bits, k_hashes=k_hashes,
        schedule=schedule, backend=backend, use_simplicial=use_simplicial,
        donate_ratio=ratio)
    tr = telemetry.get(tracker)
    tr.count(dispatches=1)

    def finalize(host):
        counts_h, expanded_h, dropped_h, stats_h = host
        record_stats(stats_h, tracker=tr)
        return [batch_lib.LaneResult(int(np.sum(counts_h)) > 0,
                                     int(dropped_h) > 0, int(expanded_h))]

    return engine_lib.DispatchHandle((counts, expanded, dropped, stats),
                                     finalize, tracker=tr)


def decide_sharded(g: Graph, k: int, clique=(), **kw):
    """Blocking sharded decide: launch + immediate ``result()``.

    Returns the single ``batch.LaneResult`` for the rung.
    """
    return decide_sharded_async(g, k, clique, **kw).result()[0]
