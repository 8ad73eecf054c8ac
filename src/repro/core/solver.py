"""One rung of the deepening ladder: is tw(g) <= k?  (single device)

The paper's Listing 1 inner loop (§3.1 rules 1 and 3):

  frontier = { {} }
  for level = 0 .. n - max(k+1, |C|) - 1:
      expand every S by every candidate v not in S u C,
          keeping S u {v} iff deg_S(v) <= k
      dedup (exact sort | Bloom filter)
      if frontier empty: k infeasible

``decide`` runs it as one lane of the lane program (``core.batch``), or
on the host loop (``run_level``), which snapshots every level for
``reconstruct_order`` and is the tests' reference.  Overflow of the
fixed-capacity lists drops states and marks the rung inexact (the
paper's * semantics).  ``mode="bloom"`` reproduces the paper's
Monte-Carlo dedup; ``mode="sort"`` (default) is the exact beyond-paper
variant.  The ladder over rungs and blocks is ``core.ladder``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import backend as backend_lib
from . import batch as batch_lib
from . import bitset, dedup, engine as engine_lib
from . import frontier as frontier_lib
from . import expand
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
    implementation of the Listing-1 inner loop (also used by the lane,
    shard and mesh programs) — behind the device sweep's refill rule in
    sort mode (``engine.refill``), with its ``refills`` and ``appended``
    rows counted on device."""
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


def run_level(adj_dev, fr: frontier_lib.Frontier, k: int, allowed_dev,
              *, n: int, cap: int, block: int, mode: str, use_mmw: bool,
              m_bits: int, k_hashes: int, schedule: str,
              backend: str = "jax", use_simplicial: bool = False,
              tracker=None):
    """One wavefront level: expand all states in ``fr`` into a new frontier.

    Host loop: syncs on ``fr.count`` to size the chunk loop (the lane
    program, ``engine.decide_loop``, keeps this loop on device)."""
    tr = telemetry.get(tracker)
    w = fr.w
    count = int(fr.count)
    tr.count(host_syncs=1)
    # adaptive block: early levels / small instances have tiny frontiers —
    # a fixed 1024-row block pays full padding cost per chunk (§Perf iter).
    # Rounding to powers of two bounds the number of jit signatures at
    # log2(block).
    block = max(32, min(block, batch_lib.pow2_at_least(max(count, 1))))
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

    ``engine="fused"`` runs the rung as one lane of the lane program
    (``batch.decide_lanes``): one dispatch, one host sync of its counts.
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

    if keep_levels:
        engine = "host"            # per-level snapshots need the host loop
    if engine not in ("host", "fused"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine == "fused":
        with tr.time_block("rung_s"):
            [res] = batch_lib.decide_lanes(
                [batch_lib.Lane(g, k, tuple(clique))], cap=cap, block=block,
                mode=mode, use_mmw=use_mmw, m_bits=m_bits,
                k_hashes=k_hashes, schedule=schedule, backend=backend,
                use_simplicial=use_simplicial, tracker=tr)
        return DecideResult(res.feasible, res.inexact, res.expanded, None)

    # fail before any level runs, like the lane program does — not at the
    # first level whose adapted block happens not to divide cap
    engine_lib.validate_geometry(cap, block, adaptive=True)
    w = bitset.n_words(n)
    adj_dev = jnp.asarray(g.packed())
    allowed_dev = jnp.asarray(bitset.np_allowed(n, clique))
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


# ------------------------------------------------------------------ result

@dataclasses.dataclass
class SolveResult:
    """One instance's answer (``core.ladder``): the width, whether it is
    proved, the bounds, the states expanded, and per-rung accounting."""
    width: int
    exact: bool
    lb: int
    ub: int
    expanded: int
    time_sec: float
    order: Optional[list] = None
    per_k: Optional[dict] = None
