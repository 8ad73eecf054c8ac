"""Distributed wavefront solver: the paper's single-GPU loop on a TPU mesh.

Mapping (DESIGN.md §4):

  * the frontier is sharded over the ``data`` mesh axis (and the ``pod``
    axis in multi-pod meshes) — each device owns ``cap_local`` state slots;
  * expansion + intra-chunk dedup are embarrassingly parallel (no
    collectives), executed under ``shard_map``;
  * duplicate elimination across devices uses **ownership routing**:
    every candidate state is hash-partitioned (murmur3 mod D) to a unique
    owner device via ``all_to_all``, and the owner performs an exact sorted
    dedup of everything it receives.  This replaces the paper's atomic-OR
    Bloom filter + mutex striping: with a single writer per state there is
    nothing to synchronise;
  * load balance comes from the hash itself (multinomial balance,
    O(sqrt) deviation) — the explicit analogue of the paper's observation
    that states can be processed independently.  Straggler mitigation is
    structural: every device runs the identical dense program;
  * capacity overflow (local buffer, send bucket, owner buffer) drops
    states and marks the run inexact — the paper's list-overflow semantics,
    now per shard;
  * the frontier (plus k/level cursor) can be checkpointed each level and
    restored onto a *different* device count (elastic restart).

Runs on any mesh with a ``data`` axis; CPU tests force multiple host
devices via XLA_FLAGS (see tests/test_distributed_tw.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import backend as backend_lib
from . import batch as batch_lib
from . import bitset, bloom, dedup
from . import engine as engine_lib
from . import telemetry
from .graph import Graph

U32 = jnp.uint32

# donate when max shard occupancy exceeds ratio × mean occupancy.  1.5
# tolerates the multinomial noise of hash ownership on healthy levels but
# fires on genuine skew (and on the tiny early levels, where idle shards
# are guaranteed); <= 1.0 rebalances every level.
DEFAULT_DONATE_RATIO = 1.5


# --------------------------------------------------------------- ownership

def route_states(rows: jnp.ndarray, valid: jnp.ndarray, nshards: int,
                 cap_recv: int):
    """Partition valid rows to their owner shard (murmur3 mod S).

    Returns (recv (S, cap_recv, W), counts (S,), dropped).  Rows are
    sorted by (owner, words) first, so each owner's bucket arrives
    lexicographically sorted.  Shared by the mesh program here (whose
    buckets feed a real all_to_all) and the single-device sharded engine
    in ``core.shard`` (scatter = the degenerate all_to_all).
    """
    m, w = rows.shape
    owner = (bloom.murmur3_words(rows, bloom.SEED1) % np.uint32(nshards)) \
        .astype(jnp.int32)
    owner = jnp.where(valid, owner, nshards)       # invalid rows sort last
    cols = (owner,) + tuple(rows[:, j] for j in range(w))
    srt = jax.lax.sort(cols, dimension=0, num_keys=1 + w)
    owner_s = srt[0]
    rows_s = jnp.stack(srt[1:], axis=1)
    counts = jnp.bincount(owner, length=nshards + 1)[:nshards] \
        .astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    idx = jnp.arange(m, dtype=jnp.int32)
    safe_owner = jnp.minimum(owner_s, nshards - 1)
    pos = idx - starts[safe_owner]
    ok = (owner_s < nshards) & (pos < cap_recv)
    dest = jnp.where(ok, safe_owner * cap_recv + pos, nshards * cap_recv)
    recv = jnp.zeros((nshards * cap_recv, w), dtype=U32)
    recv = recv.at[dest].set(rows_s, mode="drop")
    rcounts = jnp.minimum(counts, cap_recv)
    dropped = jnp.sum(counts - rcounts)
    return recv.reshape(nshards, cap_recv, w), rcounts, dropped


# ---------------------------------------------------------------- donation

def donation_plan(counts: jnp.ndarray, ratio: float):
    """Water-filling donation targets for per-shard occupancies.

    Returns (targets (S,), triggered (bool), moved (rows leaving their
    shard)).  Targets are the balanced occupancy ``total // S`` (+1 for
    the first ``total % S`` shards), so ``sum(targets) == sum(counts)``
    and no row is ever dropped by a donation.  ``triggered`` fires when
    ``max(counts) * S > ratio * total`` — pure arithmetic on the counts
    vector, so every shard (or mesh device) computes the identical plan.
    """
    s = counts.shape[0]
    total = jnp.sum(counts)
    base = total // s
    rem = total - base * s
    targets = (base + (jnp.arange(s, dtype=jnp.int32) < rem)) \
        .astype(jnp.int32)
    trig = (total > 0) & (jnp.max(counts).astype(jnp.float32) * s
                          > float(ratio) * total.astype(jnp.float32))
    moved = jnp.sum(jnp.maximum(counts - targets, 0))
    return targets, trig, moved


def record_stats(stats_h, tracker=None) -> None:
    """Count one rung's shard-health vector ``[donation_events,
    donated_rows, idle_shard_steps, peak_shard_occupancy]`` (host ints)
    into the tracker; shared by the mesh and the vmapped-shard rungs."""
    ev, moved, idle, peak = (int(x) for x in stats_h)
    tr = telemetry.get(tracker)
    tr.count(shard_donations=ev, shard_donated_rows=moved,
             shard_idle_steps=idle)
    tr.gauge_max("shard_peak_occupancy", peak)


def make_solver_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), ("data",))


# ------------------------------------------------------------ device-local fn

def _local_expand(adj, states, count, k, allowed, *, n, cap_local, block,
                  use_mmw, use_simplicial, schedule, backend):
    """Expand the local states in block chunks; returns (buf, count, drops).

    Pure per-device computation (no collectives) — the shared
    ``engine.chunk_sweep`` (identical math to the single-device path),
    bound by the device-resident local count: no host participation, no
    wasted chunks, and one compiled program regardless of frontier size
    (the old ``lax.scan`` needed a host sync per level to pick its trip
    count, and a recompile per trip-count bucket).  Cross-chunk dedup is
    deferred to the owner device after routing.
    """
    return engine_lib.chunk_sweep(
        adj, allowed, k, states, count, block, n=n, cap=cap_local,
        mode="sort", use_mmw=use_mmw, m_bits=1, k_hashes=1,
        schedule=schedule, backend=backend, use_simplicial=use_simplicial,
        max_chunks=-(-cap_local // block), cross_dedup=False)[:3]


def _build_buckets(rows, count, ndev, cap_send, w):
    """Group valid rows by owner device -> (send (ndev, cap_send, W),
    send_counts (ndev,), dropped).  Thin prefix-count adapter over the
    shared ownership router ``route_states`` (same hash, same sort/scatter
    on a single device's shards and on the mesh)."""
    del w
    valid = jnp.arange(rows.shape[0], dtype=jnp.int32) < count
    return route_states(rows, valid, ndev, cap_send)


def _donate(buf, cnt, counts_all, me, *, ndev, cap_local, cap_send, w,
            axes, donate_ratio):
    """Mesh work donation: rebalance post-dedup rows across devices.

    Every device computes the identical water-filling plan from the
    all-gathered counts (``donation_plan``), so the transfer matrix
    ``T[d, e]`` needs no negotiation: device d sends its surplus rows
    (beyond its keep target) in contiguous runs to the deficit devices via
    a second ``all_to_all``, and reads its own receive counts from
    ``T[:, me]`` locally.  Per-edge transfers are clamped to ``cap_send``
    (partial donation; the remainder simply stays at the donor), so no
    state is ever dropped by a donation.  Returns
    (buf, cnt, stats (4,) [triggered, rows_moved, idle, peak]) with stats
    identical on every device (pure functions of ``counts_all``).
    """
    targets, trig, _moved = donation_plan(counts_all, donate_ratio)
    give = jnp.maximum(counts_all - targets, 0)
    take = jnp.maximum(targets - counts_all, 0)
    zero1 = jnp.zeros((1,), jnp.int32)
    gg = jnp.concatenate([zero1, jnp.cumsum(give).astype(jnp.int32)])
    gt = jnp.concatenate([zero1, jnp.cumsum(take).astype(jnp.int32)])
    t_mat = jnp.maximum(
        0, jnp.minimum(gg[1:, None], gt[None, 1:])
        - jnp.maximum(gg[:-1, None], gt[None, :-1]))
    t_mat = jnp.where(trig, jnp.minimum(t_mat, cap_send), 0) \
        .astype(jnp.int32)

    row_t = t_mat[me]                         # rows I send to each device
    keep = cnt - jnp.sum(row_t)
    off = jnp.concatenate([zero1, jnp.cumsum(row_t).astype(jnp.int32)])
    flat = jnp.arange(ndev * cap_send, dtype=jnp.int32)
    eidx, j = flat // cap_send, flat % cap_send
    src = keep + off[eidx] + j
    sval = j < row_t[eidx]
    send = jnp.where(sval[:, None],
                     buf[jnp.clip(src, 0, cap_local - 1)], 0).astype(U32)
    recv = jax.lax.all_to_all(send.reshape(ndev, cap_send, w), axes,
                              split_axis=0, concat_axis=0, tiled=False)
    rcnt = t_mat[:, me]                       # rows I receive, known locally
    rrows = recv.reshape(ndev * cap_send, w)
    rval = j < rcnt[eidx]
    mask_keep = jnp.arange(cap_local, dtype=jnp.int32) < keep
    buf = jnp.where(mask_keep[:, None], buf, 0)
    buf, _, _ = dedup.compact(rrows, rval, buf, keep)
    cnt = keep + jnp.sum(rcnt)

    stats = jnp.stack([trig.astype(jnp.int32), jnp.sum(t_mat),
                       jnp.sum((counts_all == 0).astype(jnp.int32)),
                       jnp.max(counts_all)])
    return buf, cnt, stats


def _make_level_shardmap(mesh, *, n, cap_local, block, cap_send,
                         use_mmw, use_simplicial, schedule, backend,
                         donate_ratio=None):
    """The per-level SPMD program: local expand -> ownership all_to_all ->
    owner dedup -> (threshold donation).  Returned un-jitted so it can be
    embedded either in a host-driven per-level jit or inside the fused
    while_loop.  Outputs (states, counts, dropped, stats) with ``stats``
    the replicated shard-health vector of ``shard.sharded_decide_loop``
    (zeros when donation is disabled — the plan needs the same all_gather
    the stats do)."""
    ndev = mesh.devices.size
    axes = tuple(mesh.axis_names)

    def local_fn(adj, states, count, k, allowed):
        # shard_map views: states (cap_local, W), count (1,)
        w = adj.shape[-1]
        out, ocount, drop_local = _local_expand(
            adj, states, count[0], k, allowed, n=n, cap_local=cap_local,
            block=block, use_mmw=use_mmw, use_simplicial=use_simplicial,
            schedule=schedule, backend=backend)
        # ownership routing (all_to_all over the flattened device axes)
        send, send_counts, drop_send = _build_buckets(
            out, ocount, ndev, cap_send, w)
        recv = jax.lax.all_to_all(send, axes, split_axis=0, concat_axis=0,
                                  tiled=False)
        rcounts = jax.lax.all_to_all(send_counts, axes, split_axis=0,
                                     concat_axis=0, tiled=False)
        rows = recv.reshape(ndev * cap_send, w)
        rvalid = (jnp.arange(cap_send, dtype=jnp.int32)[None, :]
                  < rcounts[:, None]).reshape(-1)
        buf, cnt, drop_own = dedup.dedup_compact(rows, rvalid, cap_local)
        if donate_ratio is not None:
            me = jnp.asarray(0, jnp.int32)
            for ax in axes:
                me = me * mesh.shape[ax] + jax.lax.axis_index(ax)
            counts_all = jax.lax.all_gather(cnt, axes).astype(jnp.int32)
            buf, cnt, stats = _donate(
                buf, cnt, counts_all, me, ndev=ndev, cap_local=cap_local,
                cap_send=cap_send, w=w, axes=axes,
                donate_ratio=donate_ratio)
        else:
            stats = jnp.zeros((4,), jnp.int32)
        dropped = (drop_local + drop_send + drop_own)[None]
        return (buf, cnt[None].astype(jnp.int32),
                dropped.astype(jnp.int32), stats)

    spec_sharded = P(axes)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), spec_sharded, spec_sharded, P(), P()),
        out_specs=(spec_sharded, spec_sharded, spec_sharded, P()),
        check_vma=False)


_DIST_FN_CACHE: dict = {}


def _dist_fns(mesh, *, n, cap_local, block, cap_send, use_mmw,
              use_simplicial, schedule, backend, donate_ratio=None):
    """(jitted per-level fn, jitted fused decide fn) for one config.

    Module-level cache: jit compilation caches key on function identity, so
    rebuilding the closures per ``decide`` call (the old behaviour) forced
    a retrace for every k of the iterative deepening."""
    key = (mesh, n, cap_local, block, cap_send, use_mmw, use_simplicial,
           schedule, backend, donate_ratio)
    if key in _DIST_FN_CACHE:
        return _DIST_FN_CACHE[key]

    level_sm = _make_level_shardmap(
        mesh, n=n, cap_local=cap_local, block=block, cap_send=cap_send,
        use_mmw=use_mmw, use_simplicial=use_simplicial, schedule=schedule,
        backend=backend, donate_ratio=donate_ratio)

    def mesh_decide_fn(adj, states, counts, k, target, allowed):
        """Whole decide loop device-resident: mirrors engine.decide_loop
        with the level step replaced by the sharded SPMD program."""
        zero = jnp.asarray(0, jnp.int32)

        def cond(c):
            _states, counts, level, _expanded, _dropped, _stats = c
            return (level < target) & (jnp.sum(counts) > 0)

        def body(c):
            states, counts, level, expanded, dropped, stats = c
            expanded = expanded + jnp.sum(counts)
            states, counts, drop, lstats = level_sm(adj, states, counts, k,
                                                    allowed)
            stats = jnp.stack([stats[0] + lstats[0], stats[1] + lstats[1],
                               stats[2] + lstats[2],
                               jnp.maximum(stats[3], lstats[3])])
            return (states, counts, level + 1, expanded,
                    dropped + jnp.sum(drop), stats)

        _states, counts, _level, expanded, dropped, stats = \
            jax.lax.while_loop(cond, body, (states, counts, zero, zero,
                                            zero, jnp.zeros((4,), jnp.int32)))
        return jnp.sum(counts) > 0, dropped, expanded, stats

    fns = (jax.jit(level_sm), jax.jit(mesh_decide_fn))
    _DIST_FN_CACHE[key] = fns
    return fns


# ------------------------------------------------------------------- driver

@dataclasses.dataclass
class DistFrontier:
    states: jax.Array        # (D*cap_local, W) sharded over mesh axes
    counts: jax.Array        # (D,) int32 sharded
    level: int
    k: int


def init_frontier(mesh, cap_local, w):
    """The DP root {∅} as a frontier sharded over the mesh: states
    (devices · cap_local, W) split row-wise, one count per device."""
    axes = tuple(mesh.axis_names)
    ndev = mesh.devices.size
    sh_states = NamedSharding(mesh, P(axes))
    sh_counts = NamedSharding(mesh, P(axes))
    states = jnp.zeros((ndev * cap_local, w), dtype=U32)
    counts = np.zeros((ndev,), dtype=np.int32)
    counts[0] = 1                                  # the empty set, on dev 0
    return (jax.device_put(states, sh_states),
            jax.device_put(jnp.asarray(counts), sh_counts))


def decide_launch(g: Graph, k: int, clique, mesh: Mesh, *,
                  cap_local: int, block: int, use_mmw: bool = False,
                  use_simplicial: bool = False,
                  schedule: str = "doubling", backend: str = "jax",
                  donate_ratio: Optional[float]
                  = DEFAULT_DONATE_RATIO,
                  resume: Optional[dict] = None,
                  tracker=None) -> engine_lib.DispatchHandle:
    """Enqueue one fused mesh-sharded decide; return its in-flight handle.

    The mesh twin of ``shard.decide_sharded_async``: one dispatch runs the
    whole rung device-resident (level loop, ownership all_to_all, owner
    dedup, threshold donation), and ``handle.result()`` performs the one
    deferred host sync, yielding a one-element ``[batch.LaneResult]`` so a
    mesh rung drops into the same serving/sync machinery as a lane or a
    vmapped shard group.  This is the path that unifies the distributed
    solver with the serving pool: ``decide_distributed(engine="fused")``
    is launch + immediate ``result()``."""
    backend_lib.validate(backend, mode="sort", schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial)
    n = g.n
    block = engine_lib.validate_geometry(cap_local, block)
    target = n - max(k + 1, len(clique))
    if target <= 0:
        res = [batch_lib.LaneResult(True, False, 0)]
        return engine_lib.DispatchHandle((), lambda host: res,
                                         _result=res, _done=True)
    w = bitset.n_words(n)
    ndev = mesh.devices.size
    adj_dev = jnp.asarray(g.packed())
    allowed_dev = jnp.asarray(_allowed_words(n, clique))
    cap_send = max(32, (2 * cap_local) // ndev)

    states, counts = init_frontier(mesh, cap_local, w)
    start_level, expanded0, inexact0 = 0, 0, False
    if resume is not None:
        states, counts = _restore(mesh, resume, cap_local, w)
        start_level = resume["level"]
        expanded0 = int(resume.get("expanded", 0))
        inexact0 = bool(resume.get("inexact", False))

    _level_fn, fused_fn = _dist_fns(
        mesh, n=n, cap_local=cap_local, block=block, cap_send=cap_send,
        use_mmw=use_mmw, use_simplicial=use_simplicial, schedule=schedule,
        backend=backend, donate_ratio=donate_ratio)
    feas_dev, drop_dev, exp_dev, stats_dev = fused_fn(
        adj_dev, states, counts, jnp.asarray(k, jnp.int32),
        jnp.asarray(target - start_level, jnp.int32), allowed_dev)
    tr = telemetry.get(tracker)
    tr.count(dispatches=1)

    def finalize(host):
        feas, drop, exp, stats = host
        record_stats(stats, tracker=tr)
        return [batch_lib.LaneResult(bool(feas),
                                     inexact0 or int(drop) > 0,
                                     expanded0 + int(exp))]

    return engine_lib.DispatchHandle(
        (feas_dev, drop_dev, exp_dev, stats_dev), finalize, tracker=tr)


def _allowed_words(n: int, clique) -> np.ndarray:
    allowed = np.asarray(bitset.full(n)).copy()
    for v in clique:
        allowed[v >> 5] &= ~np.uint32(np.uint32(1) << np.uint32(v & 31))
    return allowed


def decide_distributed(g: Graph, k: int, clique: list, mesh: Mesh, *,
                       cap_local: int, block: int, use_mmw: bool = False,
                       use_simplicial: bool = False,
                       schedule: str = "doubling", backend: str = "jax",
                       checkpoint_cb=None, resume: Optional[dict] = None,
                       engine: str = "fused",
                       donate_ratio: Optional[float]
                       = DEFAULT_DONATE_RATIO,
                       tracker=None):
    """Distributed decision: is tw(g) <= k?  Mirrors solver.decide.

    ``engine="fused"`` runs the whole level loop as one device-resident
    program (the sharded analogue of ``engine.decide_loop``): zero host
    syncs until the verdict.  Per-level checkpointing needs host snapshots,
    so a ``checkpoint_cb`` forces the host loop.  ``donate_ratio`` tunes
    the per-level work donation (None disables it)."""
    tr = telemetry.get(tracker)
    if engine == "fused" and checkpoint_cb is None:
        with tr.time_block("rung_s"):
            res = decide_launch(
                g, k, clique, mesh, cap_local=cap_local, block=block,
                use_mmw=use_mmw, use_simplicial=use_simplicial,
                schedule=schedule, backend=backend,
                donate_ratio=donate_ratio, resume=resume,
                tracker=tr).result()[0]
        return res.feasible, res.inexact, res.expanded

    backend_lib.validate(backend, mode="sort", schedule=schedule,
                         use_mmw=use_mmw, use_simplicial=use_simplicial)
    n = g.n
    block = engine_lib.validate_geometry(cap_local, block)
    target = n - max(k + 1, len(clique))
    if target <= 0:
        return True, False, 0
    w = bitset.n_words(n)
    ndev = mesh.devices.size
    adj_dev = jnp.asarray(g.packed())
    allowed_dev = jnp.asarray(_allowed_words(n, clique))
    cap_send = max(32, (2 * cap_local) // ndev)

    states, counts = init_frontier(mesh, cap_local, w)
    start_level, expanded, inexact = 0, 0, False
    if resume is not None:
        states, counts = _restore(mesh, resume, cap_local, w)
        start_level = resume["level"]
        expanded = int(resume.get("expanded", 0))
        inexact = bool(resume.get("inexact", False))

    level_fn, _fused_fn = _dist_fns(
        mesh, n=n, cap_local=cap_local, block=block, cap_send=cap_send,
        use_mmw=use_mmw, use_simplicial=use_simplicial, schedule=schedule,
        backend=backend, donate_ratio=donate_ratio)
    kdev = jnp.asarray(k, jnp.int32)

    for level in range(start_level, target):
        counts_h = np.asarray(counts)
        tr.count(host_syncs=1)
        expanded += int(counts_h.sum())              # states popped this level
        with tr.time_block("level_s"):
            states, counts, dropped, stats = level_fn(
                adj_dev, states, counts, kdev, allowed_dev)
            tr.count(dispatches=1)
            inexact |= int(jnp.sum(dropped)) > 0
            total = int(jnp.sum(counts))
            tr.count(host_syncs=2)
        # frontier occupancy across the mesh vs the planned local capacity
        tr.gauge_max("frontier_peak_rows", total)
        record_stats(np.asarray(stats), tracker=tr)
        if checkpoint_cb is not None:
            checkpoint_cb(dict(level=level + 1, k=k, expanded=expanded,
                               inexact=inexact,
                               states=np.asarray(states),
                               counts=np.asarray(counts)))
        if total == 0:
            return False, inexact, expanded
    return True, inexact, expanded


def _restore(mesh, ckpt: dict, cap_local: int, w: int):
    """Elastic restore: reshard host rows onto the current mesh size."""
    axes = tuple(mesh.axis_names)
    ndev = mesh.devices.size
    old_counts = ckpt["counts"]
    old_states = ckpt["states"]
    old_ndev = len(old_counts)
    old_cap = old_states.shape[0] // old_ndev
    rows = []
    for d in range(old_ndev):
        c = int(old_counts[d])
        rows.append(old_states[d * old_cap: d * old_cap + c])
    rows = np.concatenate(rows, axis=0) if rows else np.zeros((0, w), np.uint32)
    # round-robin rows across the new device count
    states = np.zeros((ndev * cap_local, w), dtype=np.uint32)
    counts = np.zeros((ndev,), dtype=np.int32)
    for i, r in enumerate(rows):
        d = i % ndev
        if counts[d] < cap_local:
            states[d * cap_local + counts[d]] = r
            counts[d] += 1
    sh = NamedSharding(mesh, P(axes))
    return (jax.device_put(jnp.asarray(states), sh),
            jax.device_put(jnp.asarray(counts), sh))
