"""Pallas TPU kernel: wavefront state expansion.

Replaces the paper's thread-per-(state, vertex) DFS (Listing 1, lines 7-19)
with a divergence-free bitset computation on the VPU:

  * states lie across vector lanes (``repro.kernels.common``): every
    bitset operation below is elementwise over 128·S states at once;
  * the packed (n, W) uint32 adjacency matrix is pinned in SMEM and
    read as scalars — the analogue of the paper putting adjacency lists in
    constant memory;
  * each grid step processes a ``block`` of state rows resident in VMEM
    (the analogue of the work-group size knob from Table 2);
  * the component closure is Warshall's algorithm with a static trip
    count of n pivots — zero branch divergence by construction — and
    each pivot updates all n rows at once (rows lie on the untiled
    leading axis of a VMEM ref).

``reach_block`` is the factored kernel body: the closure/reach/degree math
shared with the fused wavefront kernel (``repro.kernels.wavefront``), which
composes it with feasibility masking and the pruning rules in one VMEM
pass.  This standalone kernel emits only deg_S(v); child construction /
dedup happen outside.  Validated against ``ref.expand_ref`` and the python
DFS oracle (tests/test_kernels_expand.py).
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


N_SCRATCH = 5   # adjv, z, nb, reach, q — each (n, W, S, 128) uint32


def reach_block(adj_ref, s, adjv_ref, z_ref, nb_ref, reach_ref, q_ref, *,
                n: int):
    """Closure + reach + degrees for a block of states, all in VMEM.

    adj_ref: SMEM (n, W) uint32 adjacency words; s: W arrays (S, 128) of
    state words.  The other refs are (n, W, S, 128) VMEM scratch, row v on
    the leading axis: adjv_ref gets the adjacency broadcast over states,
    reach_ref[v] the eliminated-graph adjacency row of v under each state
    and q_ref[v] the set q = reach \\ S \\ {v} (the paper's Q(S, v)).
    Returns deg (n, S, 128) int32, deg[v] = |q[v]|.  Rows for v in S are
    garbage; callers mask them.
    """
    w = len(s)
    zero = jnp.zeros_like(s[0])
    for v in range(n):
        for j in range(w):
            adjv_ref[v, j] = zero | adj_ref[v, j]
    eye = [common.eye(n, j, s[0]) for j in range(w)]
    in_s = mask_of(common.unpack(s, n))

    # z[i] = (N(i) ∩ S) ∪ {i} for i in S, else ∅
    for j in range(w):
        z_ref[:, j] = in_s & ((adjv_ref[:, j] & s[j][None]) | eye[j])
        nb_ref[:, j] = jnp.zeros_like(in_s)

    def close(c, cw, sh):                  # Warshall: z[i] |= z[c], c ∈ z[i]
        zc = z_ref[c]
        m = mask_of((z_ref[:, cw] >> sh) & np.uint32(1))
        for j in range(w):
            z_ref[:, j] = z_ref[:, j] | (zc[j][None] & m)

    common.pivot_loop(n, w, close)         # z[i] = component of i in G[S]

    def spread(c, cw, sh):                 # nb[i] = N(component(i))
        ac = adjv_ref[c]
        m = mask_of((z_ref[:, cw] >> sh) & np.uint32(1))
        for j in range(w):
            nb_ref[:, j] = nb_ref[:, j] | (ac[j][None] & m)

    common.pivot_loop(n, w, spread)

    def gather(i, iw, sh):                 # reach[v] |= nb[i], i ∈ N(v)
        # nb[i] = ∅ for i ∉ S (z[i] = ∅), so N(v) ∩ S needs no S mask
        nbi = nb_ref[i]
        m = mask_of((adjv_ref[:, iw] >> sh) & np.uint32(1))
        for j in range(w):
            reach_ref[:, j] = reach_ref[:, j] | (nbi[j][None] & m)

    reach_ref[...] = adjv_ref[...]
    common.pivot_loop(n, w, gather)

    q = []
    for j in range(w):
        q.append(reach_ref[:, j] & ~s[j][None] & ~eye[j])
        q_ref[:, j] = q[j]
    return common.popcount(q)


def _expand_kernel(adj_ref, states_ref, deg_ref, *scratch, n: int):
    s = [states_ref[j] for j in range(states_ref.shape[0])]
    deg_ref[...] = reach_block(adj_ref, s, *scratch, n=n)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def expand_degrees_pallas(adj: jnp.ndarray, states: jnp.ndarray, *, n: int,
                          block: int, interpret: bool):
    """deg_S(v) for every state row and vertex v: adj (n, W), states
    (B, W) -> (B, n) int32.  ``block`` is state rows of 128 per grid step
    (``common.lane_geometry``)."""
    b, w = states.shape
    rows, step = common.lane_geometry(b, block)
    kernel = functools.partial(_expand_kernel, n=n)
    deg = pl.pallas_call(
        kernel,
        grid=(rows // step,),
        in_specs=[
            common.smem((n, w)),                       # adjacency: pinned
            common.lane_tile(step, w),                 # states tile
        ],
        out_specs=common.lane_tile(step, n),
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n, w, step, LANES), U32)] * N_SCRATCH,
        interpret=interpret,
    )(adj, common.to_lanes(states, rows))
    return common.from_lanes(deg, b)
