"""Pallas TPU kernel: minor-min-width lower bound (paper §3.3).

The GPU version tracks degrees + a disjoint-set forest and re-runs DFS over
the original graph per contraction.  The TPU form keeps the per-state
eliminated-graph adjacency (the reach matrix, already produced by the
expansion kernel) as n bitset rows in VMEM and performs each contraction
as pure bitset algebra — column clear + column select + two row writes —
with a **static trip count** of n-1 contraction steps and per-state
done-masking instead of divergent early exit (the branch-divergence story
of the paper's §4.5, resolved structurally).  States lie across lanes
(``repro.kernels.common``), so the per-state argmins are elementwise
reductions over the leading vertex axis.

``mmw_block`` is the factored kernel body; the fused wavefront kernel
(``repro.kernels.wavefront``) reuses it on the reach rows it already holds
in VMEM, so the prune never materialises reach in HBM.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import LANES, U32, mask_of

BIG = 1 << 20          # python int: pallas kernels cannot capture arrays


def _first_argmin(d, vid):
    """Per-state (min, first index of the min) over the leading axis."""
    best = jnp.min(d, axis=0)
    return jnp.min(jnp.where(d == best[None], vid, d.shape[0]), axis=0), best


def _row(rows, at, vid):
    """rows (n, S, 128) -> (S, 128): row ``at[state]`` of every state."""
    picked = jnp.where(vid == at[None], rows, np.uint32(0))
    # one nonzero row per state, so a sum is exact; Mosaic reduces signed
    # integers only
    total = jnp.sum(jax.lax.bitcast_convert_type(picked, jnp.int32), axis=0)
    return jax.lax.bitcast_convert_type(total, U32)


def mmw_block(reach_ref, s, kk, adjm_ref, *, n: int):
    """Batched minor-min-width bounds for a block of states.

    reach_ref (n, W, S, 128) eliminated-graph rows; s: W arrays (S, 128) of
    state words; kk scalar int32; adjm_ref (n, W, S, 128) scratch.
    Returns (S, 128) int32 bounds; values freeze once > kk, matching
    ``repro.core.mmw.mmw_bound``'s early exit bit for bit.
    """
    w = len(s)
    vid = common.vertex_iota(n, s[0])
    eye = [common.eye(n, j, s[0]) for j in range(w)]
    active = [np.uint32(common.full_word(n, j)) & ~s[j] for j in range(w)]
    live_row = mask_of(common.unpack(active, n))
    for j in range(w):
        adjm_ref[:, j] = live_row & reach_ref[:, j] & active[j][None] & ~eye[j]
    lb = jnp.zeros(s[0].shape, jnp.int32)
    nact = common.popcount(active)

    def step(_, carry):
        active, lb, nact = list(carry[:w]), carry[w], carry[w + 1]
        live = (nact > 1) & (lb <= kk)                     # done-masking
        rows = [adjm_ref[:, j] for j in range(w)]
        d = jnp.where(common.unpack(active, n) != 0, common.popcount(rows),
                      BIG)                                 # (n, S, 128)
        v, dv = _first_argmin(d, vid)
        # second-smallest active degree is also a lower bound [BK'11]
        second = jnp.min(jnp.where(vid == v[None], BIG, d), axis=0)
        lb_new = jnp.maximum(lb, jnp.where(nact >= 2,
                                           jnp.minimum(second, BIG - 1), 0))
        # min-degree neighbour of v (v itself when isolated -> deactivate v)
        vrow = [_row(r, v, vid) for r in rows]
        dn = jnp.where(common.unpack(vrow, n) != 0, d, BIG)
        u = jnp.where(dv > 0, _first_argmin(dn, vid)[0], v)
        urow = [_row(r, u, vid) for r in rows]
        uhot = [common.onehot(u, j) for j in range(w)]
        vhot = [common.onehot(v, j) for j in range(w)]
        merged = [(vrow[j] | urow[j]) & active[j] & ~uhot[j] & ~vhot[j]
                  for j in range(w)]
        in_merged = mask_of(common.unpack(merged, n))
        for j in range(w):
            row = rows[j] & ~uhot[j][None]                 # clear column u
            row = (row & ~vhot[j][None]) | (vhot[j][None] & in_merged)
            row = jnp.where(vid == v[None], merged[j][None], row)
            row = jnp.where(vid == u[None], np.uint32(0), row)
            adjm_ref[:, j] = jnp.where(live[None], row, rows[j])
        active = [jnp.where(live, a & ~h, a) for a, h in zip(active, uhot)]
        lb = jnp.where(live, lb_new, lb)
        nact = jnp.where(live, nact - 1, nact)
        return (*active, lb, nact)

    carry = jax.lax.fori_loop(0, max(n - 1, 1), step, (*active, lb, nact))
    return carry[w]


def _mmw_kernel(k_ref, reach_ref, states_ref, lb_ref, adjm_ref, *, n: int):
    s = [states_ref[j] for j in range(states_ref.shape[0])]
    lb_ref[...] = mmw_block(reach_ref, s, k_ref[0, 0], adjm_ref, n=n)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def mmw_bounds_pallas(reach, states, k, *, n: int, block: int,
                      interpret: bool):
    """MMW lower bounds for a batch of states.

    reach (B, n, W) uint32 eliminated-graph rows; states (B, W); k (1, 1)
    int32.  Returns (B,) int32 bounds (exceeding k means prunable; values
    freeze once > k, matching core.mmw early exit).  ``block`` is state
    rows of 128 per grid step (``common.lane_geometry``).
    """
    b, _, w = reach.shape
    rows, step = common.lane_geometry(b, block)
    kernel = functools.partial(_mmw_kernel, n=n)
    lb = pl.pallas_call(
        kernel,
        grid=(rows // step,),
        in_specs=[
            common.smem((1, 1)),
            common.lane_tile(step, n, w),
            common.lane_tile(step, w),
        ],
        out_specs=common.lane_tile(step),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n, w, step, LANES), U32)],
        interpret=interpret,
    )(k, common.to_lanes(reach, rows), common.to_lanes(states, rows))
    return common.from_lanes(lb, b)
