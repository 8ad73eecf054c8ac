"""Sharded solve tests (``repro.core.shard``, intra-request scale-out).

Parity is the whole contract: splitting one rung's frontier across S
shards (owner-hash routing + single-writer dedup + work donation) must
leave the verdict, the ``expanded`` count, and the per-rung ladder trace
bit-identical to the single-lane fused engine — on every axis of the
support matrix, and under forced donation skew.  The multi-device mesh
variant needs XLA_FLAGS set before jax initialises, so it runs in a
subprocess like ``test_distributed_tw``.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.core import bloom, engine, graph, ladder, shard  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK = 1 << 6


def _run(script: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def _parity(ref, res, ctx):
    assert (res.width, res.exact, res.expanded, res.per_k) == \
        (ref.width, ref.exact, ref.expanded, ref.per_k), (ctx, res, ref)


# ------------------------------------------------------------ unit helpers

def test_route_states_partitions_losslessly():
    rng = np.random.default_rng(7)
    m, w, s, cap = 64, 2, 4, 64
    rows = jnp.asarray(rng.integers(0, 2**32, size=(m, w), dtype=np.uint32))
    valid = jnp.asarray(rng.random(m) < 0.8)
    recv, counts, dropped = shard.route_states(rows, valid, s, cap)
    recv, counts = np.asarray(recv), np.asarray(counts)
    assert int(dropped) == 0
    assert int(counts.sum()) == int(np.asarray(valid).sum())
    live = sorted(map(tuple, np.asarray(rows)[np.asarray(valid)]))
    got = sorted(tuple(recv[d, i]) for d in range(s)
                 for i in range(counts[d]))
    assert got == live
    owner = np.asarray(bloom.murmur3_words(rows, bloom.SEED1)) % s
    owner_of = {tuple(r): int(o) for r, o in zip(np.asarray(rows), owner)}
    for d in range(s):
        bucket = [tuple(recv[d, i]) for i in range(counts[d])]
        # each owner's bucket arrives sorted and owned by d
        assert bucket == sorted(bucket)
        assert all(owner_of[row] == d for row in bucket)


def test_donation_plan_triggers_on_skew_only():
    skewed = jnp.asarray([10, 0, 0, 0], jnp.int32)
    targets, trig, moved = shard.donation_plan(skewed, 1.5)
    assert bool(trig) and int(moved) == 10 - int(np.asarray(targets)[0])
    assert int(jnp.sum(targets)) == 10
    balanced = jnp.asarray([5, 5, 6, 5], jnp.int32)
    _, trig, _ = shard.donation_plan(balanced, 1.5)
    assert not bool(trig)
    empty = jnp.asarray([0, 0, 0, 0], jnp.int32)
    _, trig, _ = shard.donation_plan(empty, 1.5)
    assert not bool(trig)


# --------------------------------------------------------- parity matrix

# (backend, mode, use_mmw, use_simplicial) — the shard-supported surface
CFGS = [
    ("jax", "sort", False, False),
    ("jax", "bloom", False, False),
    ("jax", "sort", True, False),
    ("jax", "sort", False, True),
    ("pallas", "sort", False, False),
]


@pytest.mark.parametrize("backend,mode,mmw,simp", CFGS)
def test_sharded_solve_bit_parity_matrix(backend, mode, mmw, simp):
    g = graph.REGISTRY["petersen"]()
    kw = dict(block=BLOCK, backend=backend, mode=mode, use_mmw=mmw,
              use_simplicial=simp)
    ref = ladder.solve(g, **kw)
    assert ref.width == 4
    for s in (2, 3):
        res = ladder.solve(g, shards=s, **kw)
        _parity(ref, res, (backend, mode, mmw, simp, s))


def test_sharded_solve_parity_across_instances():
    for name, want in [("myciel3", 5), ("queen5_5", 18)]:
        g = graph.REGISTRY[name]()
        ref = ladder.solve(g, block=BLOCK)
        assert ref.width == want
        for s in (2, 4):
            _parity(ref, ladder.solve(g, shards=s, block=BLOCK), (name, s))


def test_forced_skew_donation_triggers_and_preserves_parity():
    g = graph.REGISTRY["myciel3"]()
    ref = ladder.solve(g, block=BLOCK)
    engine.reset_counters()
    # ratio <= 1.0 rebalances every level: the donation path runs hot
    res = ladder.solve(g, shards=4, block=BLOCK, donate_ratio=1.0)
    assert engine.COUNTERS["shard_donations"] > 0
    assert engine.COUNTERS["shard_donated_rows"] > 0
    _parity(ref, res, "forced-skew")


# ------------------------------------------------------------ exact alias

def test_shards1_and_lanes1_are_exact_aliases():
    g = graph.REGISTRY["petersen"]()
    engine.reset_counters()
    ref = ladder.solve(g, block=BLOCK)
    c_ref = dict(engine.COUNTERS)
    for kw in ({"shards": 1}, {"lanes": 1}):
        engine.reset_counters()
        res = ladder.solve(g, block=BLOCK, **kw)
        # not just equal results: the identical engine path — same
        # dispatch/sync/shard counter trace as the plain call
        assert dict(engine.COUNTERS) == c_ref, (kw, engine.COUNTERS, c_ref)
        _parity(ref, res, kw)
        assert res.order == ref.order and res.lb == ref.lb \
            and res.ub == ref.ub


def test_shards_reject_unsupported_combos():
    from repro.core import backend as backend_lib
    g = graph.REGISTRY["petersen"]()
    with pytest.raises(backend_lib.BackendCapabilityError):
        ladder.solve(g, shards=0, block=BLOCK)


# ---------------------------------------------------------------- mesh

def test_mesh_sharded_rung_matches_single_lane():
    stdout = _run("""
        import jax
        from repro.core import bounds, distributed, graph, shard, solver
        mesh = distributed.make_solver_mesh()
        assert mesh.devices.size == 8
        g = graph.REGISTRY["petersen"]()
        clique = bounds.greedy_max_clique(g)
        for k in (3, 4):
            ref = solver.decide(g, k, clique, cap=1 << 12, block=1 << 6,
                                mode="sort", use_mmw=False,
                                m_bits=1 << 24, k_hashes=17,
                                schedule="while")
            res = shard.decide_sharded(g, k, clique, shards=8, mesh=mesh,
                                       cap=1 << 9, block=1 << 6)
            assert res.feasible == ref.feasible, (k, res, ref)
            assert not res.inexact
            assert res.expanded == ref.expanded, (k, res, ref)
        print("MESH-RUNG-OK")
    """)
    assert "MESH-RUNG-OK" in stdout


def test_vmapped_shards_match_under_forced_devices():
    # the CI job runs this file under 8 forced host devices; the vmapped
    # (mesh-free) shard path must be device-count independent
    stdout = _run("""
        from repro.core import graph, ladder
        g = graph.REGISTRY["myciel3"]()
        ref = ladder.solve(g, block=1 << 6)
        res = ladder.solve(g, shards=4, block=1 << 6)
        assert (res.width, res.exact, res.expanded, res.per_k) == \\
            (ref.width, ref.exact, ref.expanded, ref.per_k), (res, ref)
        print("VMAP-8DEV-OK")
    """)
    assert "VMAP-8DEV-OK" in stdout
