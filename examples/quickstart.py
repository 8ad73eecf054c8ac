"""Quickstart: compute the treewidth of a graph with the public API.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import graph, ladder, solver

# build a graph (generators, DIMACS files, or edge lists)
g = graph.queen(5)                       # 5x5 queen graph, tw = 18
print(f"graph {g.name}: {g.n} vertices, {g.n_edges} edges")

# solve: iterative-deepening wavefront DP (paper Listing 1) with exact
# sort-based dedup.  reconstruct=True returns a certified elimination
# order — it composes with the default preprocessing (safe-separator
# blocks are reconstructed individually and stitched back through the
# preprocess vertex maps)
res = ladder.solve(g, cap=1 << 16, block=1 << 10, reconstruct=True)
print(f"treewidth = {res.width} (exact={res.exact})")
print(f"explored {res.expanded} states in {res.time_sec:.2f}s")

# the elimination order is a checkable certificate
width = solver.order_width(g, res.order)
print(f"certificate: replaying the order gives width {width}")
assert width == res.width

# speculative deepening: decide several widths per dispatch through the
# multi-lane engine (same results, fewer dispatches — see core/batch.py;
# ladder.solve_many batches across whole instance suites the same way)
res_lanes = ladder.solve(g, cap=1 << 16, block=1 << 10, lanes=4)
assert res_lanes.width == res.width

# paper-faithful Bloom-filter dedup (Monte Carlo) for comparison
res_bloom = ladder.solve(g, cap=1 << 16, block=1 << 10, mode="bloom",
                         m_bits=1 << 22)
print(f"bloom mode: treewidth = {res_bloom.width} "
      f"(expanded {res_bloom.expanded})")
