"""Distributed treewidth on a multi-device mesh (8 forced host devices):
the paper's wavefront sharded with hash-routed all_to_all dedup, with a
mid-run checkpoint + elastic restart onto fewer devices.

    PYTHONPATH=src python examples/distributed_tw.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                          # noqa: E402
from repro.core import bounds, distributed, graph, ladder   # noqa: E402

g = graph.queen(5)
mesh = distributed.make_solver_mesh()
print(f"mesh: {mesh.devices.size} devices | graph {g.name} n={g.n}")

res = ladder.solve_distributed(g, mesh, cap_local=1 << 12,
                               block=1 << 7, verbose=True)
print(f"treewidth = {res.width} (exact={res.exact}, "
      f"states={res.expanded})")

# ---- checkpoint mid-decision, resume on a SMALLER mesh (elastic restart)
clique = bounds.greedy_max_clique(g)
ckpts = []
feasible, _, _ = distributed.decide_distributed(
    g, 18, clique, mesh, cap_local=1 << 12, block=1 << 7,
    checkpoint_cb=lambda c: ckpts.append(c))
mid = ckpts[len(ckpts) // 2]
mesh4 = distributed.make_solver_mesh(jax.devices()[:4])
feasible2, _, _ = distributed.decide_distributed(
    g, 18, clique, mesh4, cap_local=1 << 13, block=1 << 7, resume=mid)
print(f"k=18 feasible: 8-dev={feasible}, resumed-on-4-dev={feasible2}")
assert feasible == feasible2
