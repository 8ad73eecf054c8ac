"""Table 2/3 analogue: work-size x memory-placement sweep.

Paper Table 2 sweeps the OpenCL work-group size (threads per SM) and global
vs shared memory.  The TPU analogues:
  * work size  -> expansion block size (states per jit'd chunk / per Pallas
    grid step);
  * global (G) vs shared (S) memory -> plain-XLA expansion ("jax", compiler-
    managed HBM streaming) vs the Pallas kernel with explicit VMEM tiling
    ("pallas"; interpret-mode on CPU, so absolute times are not meaningful
    on this host — the sweep structure is what carries to hardware).
"""
from __future__ import annotations

from repro.core import ladder

from .common import Timer, emit, get_instance

INSTANCES = ["queen5_5", "queen6_6", "myciel3"]
BLOCKS = [128, 256, 512, 1024, 2048]


def run(pallas: bool = False):
    backends = ["jax", "pallas"] if pallas else ["jax"]
    for key in INSTANCES:
        g = get_instance(key)
        base = None
        for backend in backends:
            for block in BLOCKS:
                with Timer() as t:
                    res = ladder.solve(g, cap=1 << 16, block=block,
                                       backend=backend)
                tag = "S" if backend == "pallas" else "G"
                base = base or res.width
                assert res.width == base
                emit(f"table2/{key}/{tag}/W={block}", t.seconds,
                     f"tw={res.width};exp={res.expanded}")


if __name__ == "__main__":
    import sys
    run(pallas="--pallas" in sys.argv)
