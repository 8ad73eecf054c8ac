"""Fixed-capacity frontier buffers.

The GPU implementation bounds its input/output lists at 180M states and
discards overflow (marking the run inexact).  We keep exactly those
semantics per device: a frontier is a fixed ``(cap, W)`` uint32 buffer, a
count, and a drop counter.  Fixed shapes keep every level step jit-stable;
capacity scales with the mesh in the distributed solver.

``Frontier`` is registered as a jax pytree so the device-resident engine
(``repro.core.engine``) can carry it straight through ``lax.while_loop`` /
``lax.scan`` without unpacking — the whole ``decide`` recursion then runs
as one compiled program with the frontier never leaving the device.

The same pytree doubles as the multi-lane carry of ``core.batch``: a
batched frontier simply gives every leaf a leading lane axis
(``lane_frontiers``), and vmap maps the engine over it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Frontier:
    states: jnp.ndarray      # (cap, W) uint32
    count: jnp.ndarray       # () int32
    dropped: jnp.ndarray     # () int32 — overflow accumulator for this level

    @property
    def cap(self) -> int:
        return self.states.shape[0]

    @property
    def w(self) -> int:
        return self.states.shape[1]

    # pytree protocol: all three fields are traced data (no static aux) so
    # a Frontier is a legal while_loop carry / scan state
    def tree_flatten(self):
        return (self.states, self.count, self.dropped), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def empty_frontier(cap: int, w: int) -> Frontier:
    """Frontier holding just the empty set (the DP root)."""
    return Frontier(states=jnp.zeros((cap, w), dtype=jnp.uint32),
                    count=jnp.asarray(1, dtype=jnp.int32),
                    dropped=jnp.asarray(0, dtype=jnp.int32))


def lane_frontiers(lanes: int, cap: int, w: int) -> Frontier:
    """Batched DP roots: one ``{∅}`` frontier per lane.

    Every leaf carries a leading ``lanes`` axis — states ``(lanes, cap,
    W)``, count/dropped ``(lanes,)`` — so the same ``Frontier`` pytree
    doubles as the carry of the vmapped multi-lane engine
    (``core.batch``).  The scalar-frontier ``cap``/``w`` properties do not
    apply to a batched instance (the shapes are shifted by the lane
    axis)."""
    return Frontier(states=jnp.zeros((lanes, cap, w), dtype=jnp.uint32),
                    count=jnp.ones((lanes,), dtype=jnp.int32),
                    dropped=jnp.zeros((lanes,), dtype=jnp.int32))


def shard_frontiers(shards: int, cap: int, w: int) -> Frontier:
    """One instance's DP root split across ``shards`` frontier shards.

    Unlike ``lane_frontiers`` (B independent instances, B roots) a
    sharded frontier holds ONE search: the single ``{∅}`` root lives in
    shard 0 (mirroring ``distributed.init_frontier``) and subsequent
    levels spread across shards by ownership routing (``core.shard``).
    Leaves carry a leading ``shards`` axis: states ``(S, cap, W)``,
    count/dropped ``(S,)``."""
    count = np.zeros((shards,), dtype=np.int32)
    count[0] = 1
    return Frontier(states=jnp.zeros((shards, cap, w), dtype=jnp.uint32),
                    count=jnp.asarray(count),
                    dropped=jnp.zeros((shards,), dtype=jnp.int32))


def frontier_bytes(cap: int, w: int, lanes: int = 1) -> int:
    """Device bytes of a ``(lanes, cap, W)`` uint32 frontier pool.

    This is the *resident* pool only: one level step transiently doubles
    it (the append buffer ``out`` in ``engine.expand_chunk``) and adds the
    ``(block, n, W)`` children tile.  ``batch.plan_capacity`` sizes caps
    against this number (DESIGN.md §10)."""
    return 4 * max(1, lanes) * max(1, cap) * max(1, w)


def lane_to_host(f: Frontier, lane: int) -> np.ndarray:
    """Materialise one lane's live rows from a batched frontier."""
    c = int(f.count[lane])
    return np.asarray(f.states[lane, :c])


def blank_frontier(cap: int, w: int) -> Frontier:
    return Frontier(states=jnp.zeros((cap, w), dtype=jnp.uint32),
                    count=jnp.asarray(0, dtype=jnp.int32),
                    dropped=jnp.asarray(0, dtype=jnp.int32))


def to_host(f: Frontier) -> np.ndarray:
    """Materialise the live rows (for checkpointing / reconstruction)."""
    c = int(f.count)
    return np.asarray(f.states[:c])
