"""Pallas TPU kernel: the fused Listing-1 inner loop.

The paper's 77x speedup comes from doing the *entire* per-state pipeline —
component closure, deg_S(v), the degree test, and the pruning rules — in one
on-device pass with adjacency pinned in constant memory (§3).  The unfused
kernels in ``repro.kernels.expand`` / ``repro.kernels.mmw`` reproduce the
pieces; this kernel composes their factored bodies (``reach_block``,
``mmw_block``) into a single VMEM-resident pass per state block, following
the persistent-kernel design of the GPU branch-and-reduce literature
(Yamout et al.; Almasri et al. — both keep the whole per-state pipeline in
one kernel):

  bitset closure -> deg_S(v) -> feasibility mask
                 -> simplicial collapse (optional)
                 -> MMW prune (optional)
  ==> feasible

States lie across vector lanes (``repro.kernels.common``): a grid step
holds ``block`` rows of 128 states, one (block, 128) array per bitset
word, and every per-vertex matrix is an (n, W, block, 128) VMEM scratch
ref.  The reach matrix lives only there — it is never materialised in HBM
(the pure-JAX backend streams it through HBM between ops).  The kernel
emits the feasibility mask; the child bitsets S ∪ {v} depend on nothing
the kernel computes and are formed by the wrapper.

VMEM per grid step: 5-6 scratch matrices of n·W·block·128·4 bytes —
~1.4 MiB at block=8, n=36, W=2.

Validated in interpret mode against ``ref.wavefront_ref`` (the jax backend
composition) and transitively against the python DFS/MMW/simplicial oracles
(tests/test_kernels_wavefront.py, tests/test_engine_parity.py); compiled
for a TPU v5e in tests/test_tpu_compile.py.
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
from repro.kernels.expand.kernel import N_SCRATCH, reach_block
from repro.kernels.mmw.kernel import mmw_block


def _simplicial_viol(reach_ref, q_ref, viol_ref, *, n: int, w: int):
    """viol_ref[v] != 0 iff candidate v has a witness u ∈ Q_v whose closed
    eliminated-graph neighbourhood misses part of Q_v (so Q_v is no
    clique) — the witness scan of ``repro.core.expand.simplicial_viol``,
    all candidates v at once."""
    viol_ref[...] = jnp.zeros(viol_ref.shape, U32)

    def scan(u, uw, sh):
        closed = reach_ref[u]                          # N[u] = reach[u] ∪ {u}
        has = mask_of((q_ref[:, uw] >> sh) & np.uint32(1))      # u ∈ Q_v
        miss = jnp.zeros_like(has)
        for j in range(w):
            cu = closed[j] | (np.uint32(1) << sh) if j == uw else closed[j]
            miss = miss | (q_ref[:, j] & ~cu[None])
        viol_ref[...] = viol_ref[...] | (has & miss)

    common.pivot_loop(n, w, scan)


def _wavefront_kernel(adj_ref, k_ref, allowed_ref, states_ref, valid_ref,
                      feas_ref, adjv_ref, z_ref, nb_ref, reach_ref, q_ref,
                      *viol_ref, n: int, use_mmw: bool, use_simplicial: bool):
    w = states_ref.shape[0]
    s = [states_ref[j] for j in range(w)]
    kk = k_ref[0, 0]
    deg = reach_block(adj_ref, s, adjv_ref, z_ref, nb_ref, reach_ref, q_ref,
                      n=n)
    allowed = [jnp.zeros_like(s[0]) | allowed_ref[0, j] for j in range(w)]
    feas = ((deg <= kk)
            & (common.unpack(s, n) == 0)
            & (common.unpack(allowed, n) != 0)
            & (valid_ref[...] != 0)[None])

    if use_simplicial:
        _simplicial_viol(reach_ref, q_ref, viol_ref[0], n=n, w=w)
        simp = feas & (viol_ref[0][...] == 0)
        # collapse: if any simplicial candidate, keep only the lowest-index
        vid = common.vertex_iota(n, s[0])
        first = jnp.min(jnp.where(simp, vid, n), axis=0)    # n: none
        feas = (feas & (first == n)[None]) | (vid == first[None])

    if use_mmw:
        # z is free once the reach rows exist: reuse it as MMW's scratch
        lbs = mmw_block(reach_ref, s, kk, z_ref, n=n)
        feas = feas & (lbs <= kk)[None]

    feas_ref[...] = feas.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "block", "use_mmw",
                                             "use_simplicial", "interpret"))
def wavefront_pallas(adj, states, valid, k, allowed, *, n: int,
                     block: int = 8, use_mmw: bool = False,
                     use_simplicial: bool = False, interpret: bool):
    """Fused expand + prune for a batch of states.

    adj (n, W); states (B, W); valid (B,); k (1, 1) int32; allowed (W,).
    Returns (children (B, n, W) uint32, feasible (B, n) int32) — padding
    rows come back all-infeasible.  ``block`` is state rows of 128 per
    grid step (``common.lane_geometry``): 8 fills one (8, 128) vreg per
    bitset word.
    """
    b, w = states.shape
    rows, step = common.lane_geometry(b, block)
    kernel = functools.partial(_wavefront_kernel, n=n, use_mmw=use_mmw,
                               use_simplicial=use_simplicial)
    scratch = [pltpu.VMEM((n, w, step, LANES), U32)] * N_SCRATCH
    if use_simplicial:
        scratch.append(pltpu.VMEM((n, step, LANES), U32))
    feas = pl.pallas_call(
        kernel,
        grid=(rows // step,),
        in_specs=[
            # (n, W), (1, 1), (1, W): full-array 2-D blocks stay legal
            # when vmap prepends a lane axis
            common.smem((n, w)),                       # adjacency: pinned
            common.smem((1, 1)),                       # k
            common.smem((1, w)),                       # allowed: pinned
            common.lane_tile(step, w),                 # states tile
            common.lane_tile(step),                    # valid tile
        ],
        out_specs=common.lane_tile(step, n),
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(adj, k, allowed.reshape(1, w), common.to_lanes(states, rows),
      common.to_lanes(valid.astype(jnp.int32), rows))
    children = states[:, None, :] | common.eye_words(n, w)[None]
    return children, common.from_lanes(feas, b)
