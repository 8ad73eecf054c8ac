"""Distributed solver tests.

Multi-device CPU requires XLA_FLAGS before jax initialises, so these run in
a subprocess (the main pytest process keeps its single device — smoke tests
and benches must see 1 device per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_distributed_matches_single_device():
    stdout = _run("""
        from repro.core import graph, distributed, ladder
        mesh = distributed.make_solver_mesh()
        assert mesh.devices.size == 8
        for name, want in [("petersen", 4), ("myciel3", 5), ("queen5_5", 18)]:
            g = graph.REGISTRY[name]()
            r = ladder.solve_distributed(g, mesh, cap_local=1 << 12,
                                         block=1 << 6)
            s = ladder.solve(g, cap=1 << 15, block=1 << 9)
            assert r.width == s.width == want, (name, r.width, s.width)
            assert r.exact and s.exact
            assert r.expanded == s.expanded, (name, r.expanded, s.expanded)
            assert r.per_k == s.per_k, (name, r.per_k, s.per_k)
        print("MATCH-OK")
    """)
    assert "MATCH-OK" in stdout


def test_checkpoint_restart_and_elastic():
    stdout = _run("""
        import jax
        from repro.core import graph, distributed, bounds
        g = graph.queen(5)
        mesh8 = distributed.make_solver_mesh(jax.devices())
        clique = bounds.greedy_max_clique(g)
        ckpts = []
        feas, inexact, exp = distributed.decide_distributed(
            g, 18, clique, mesh8, cap_local=1 << 11, block=1 << 6,
            checkpoint_cb=lambda c: ckpts.append(c))
        assert feas
        mid = ckpts[len(ckpts) // 2]
        # crash-restart on the same mesh
        feas2, _, _ = distributed.decide_distributed(
            g, 18, clique, mesh8, cap_local=1 << 11, block=1 << 6, resume=mid)
        assert feas2
        # elastic restart on a smaller mesh (8 -> 4 devices)
        mesh4 = distributed.make_solver_mesh(jax.devices()[:4])
        feas3, _, _ = distributed.decide_distributed(
            g, 18, clique, mesh4, cap_local=1 << 12, block=1 << 6, resume=mid)
        assert feas3
        print("RESTART-OK")
    """)
    assert "RESTART-OK" in stdout


def test_overflow_marks_inexact_distributed():
    stdout = _run("""
        from repro.core import graph, distributed, ladder
        mesh = distributed.make_solver_mesh()
        g = graph.queen(5)
        r = ladder.solve_distributed(g, mesh, cap_local=32, block=32,
                                     use_preprocess=False, use_paths=False)
        assert (not r.exact) or r.width == 18
        print("OVERFLOW-OK", r.width, r.exact)
    """)
    assert "OVERFLOW-OK" in stdout


def test_mmw_distributed():
    stdout = _run("""
        from repro.core import graph, distributed, ladder
        mesh = distributed.make_solver_mesh()
        g = graph.petersen()
        a = ladder.solve_distributed(g, mesh, cap_local=1 << 11,
                                     block=1 << 6, use_mmw=True)
        b = ladder.solve_distributed(g, mesh, cap_local=1 << 11,
                                     block=1 << 6, use_mmw=False)
        assert a.width == b.width == 4
        assert a.expanded <= b.expanded
        print("MMW-OK")
    """)
    assert "MMW-OK" in stdout


def test_fused_engine_parity_distributed():
    """The device-resident (while_loop) distributed engine must agree with
    the host-driven level loop verdict-for-verdict, including expanded
    counts and the overflow/inexact flag."""
    stdout = _run("""
        from repro.core import bounds, distributed, graph
        mesh = distributed.make_solver_mesh()
        for name, cap_local in [("petersen", 1 << 11), ("myciel3", 1 << 11),
                                ("queen5_5", 1 << 8)]:   # queen: overflows
            g = graph.REGISTRY[name]()
            clique = bounds.greedy_max_clique(g)
            for k in range(max(1, len(clique) - 1), g.n - len(clique)):
                a = distributed.decide_distributed(
                    g, k, clique, mesh, cap_local=cap_local, block=1 << 6,
                    engine="host")
                b = distributed.decide_distributed(
                    g, k, clique, mesh, cap_local=cap_local, block=1 << 6,
                    engine="fused")
                assert a == b, (name, k, a, b)
                if a[0]:
                    break
        print("DIST-PARITY-OK")
    """)
    assert "DIST-PARITY-OK" in stdout


def test_simplicial_and_backend_distributed():
    """use_simplicial is honoured (not silently dropped) by the distributed
    solver, and the pallas backend matches jax bit-for-bit there too."""
    stdout = _run("""
        from repro.core import distributed, graph, solver
        mesh = distributed.make_solver_mesh()
        g = graph.random_tree(12, 5)
        # trees collapse to a single chain per level under simplicial
        # pruning, so the flag reaching the kernels shows up as a large
        # expanded-count reduction at k=1 (bounds short-circuit solve(),
        # hence decide at fixed k)
        kw = dict(cap_local=1 << 10, block=32)
        feas_p, _, exp_plain = distributed.decide_distributed(
            g, 1, [], mesh, **kw)
        feas_s, _, exp_simp = distributed.decide_distributed(
            g, 1, [], mesh, use_simplicial=True, **kw)
        assert feas_p and feas_s
        assert exp_simp < exp_plain, (exp_simp, exp_plain)
        single = solver.decide(g, 1, [], cap=1 << 12, block=32,
                               mode="sort", use_mmw=False, m_bits=1 << 10,
                               k_hashes=4, schedule="doubling",
                               use_simplicial=True)
        assert single.feasible and single.expanded == exp_simp
        feas_pal, _, exp_pal = distributed.decide_distributed(
            g, 1, [], mesh, use_simplicial=True, backend="pallas", **kw)
        assert feas_pal and exp_pal == exp_simp
        print("SIMPLICIAL-DIST-OK")
    """, devices=4)
    assert "SIMPLICIAL-DIST-OK" in stdout
