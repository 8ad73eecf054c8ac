"""Backend registry: every wavefront op, registered once per backend.

The engine used to hard-wire the pure-JAX implementations from ``core/*``
and leave the Pallas kernels in ``kernels/*`` as validated-but-unwired
artifacts behind an ad-hoc ``impl=`` string (whose pallas path silently
dropped the reach output and crashed mid-jit under MMW/simplicial pruning).
This module collapses that split into one dispatch table:

  * each op — fused expand+prune, sort dedup, Bloom query-and-insert, and
    the standalone degree/MMW/simplicial pieces — is registered under a
    (op, backend) key with a uniform signature;
  * the solver paths (``solver.decide``, ``batch.decide_lanes``,
    ``shard``, ``distributed``) and the CLI select implementations with a
    single ``backend=`` knob;
  * unsupported combinations fail **at dispatch time** with a
    ``BackendCapabilityError`` naming the op, the backends that do support
    it, and the fix — never with a bare TypeError deep inside a jit.

Capability table (also rendered in DESIGN.md §3):

  op                 jax   pallas   notes
  wavefront_expand    ✓      ✓      pallas fuses prune rules in one VMEM pass
  expand_degrees      ✓      ✓      degrees only (no reach output)
  mmw_bound           ✓      ✓
  simplicial_mask     ✓      —      pallas form exists only fused
  sort_dedup          ✓      ✓*     *XLA sort on both (TPU sorts are
                                     XLA-native; a hand-rolled pallas sort
                                     would be slower — DESIGN.md §3)
  bloom_query_insert  ✓      ✓      pallas: packed filter, sequential grid
  bloom_make_filter   ✓      ✓      jax: uint8/bit; pallas: packed uint32

Registrations import the heavy pallas machinery lazily so that jax-only
runs never pay the ``jax.experimental.pallas`` import.
"""
from __future__ import annotations

import dataclasses
import os
import re
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

BACKENDS: Tuple[str, ...] = ("jax", "pallas")

# dedup modes understood by the engines; listed here so validation happens
# once at dispatch instead of per call site
DEDUP_MODES: Tuple[str, ...] = ("sort", "bloom")

# closure schedules of the jax reference ops.  The pallas kernels have one
# fixed closure algorithm (Warshall pivots, DESIGN §3) and ignore the
# choice; they accept only the default name, so existing callers validate
JAX_SCHEDULES: Tuple[str, ...] = ("doubling", "while", "linear", "matmul")
PALLAS_SCHEDULES: Tuple[str, ...] = ("doubling",)

# the pallas Bloom kernel holds the whole filter in VMEM twice (input and
# output block); at 2^29 bits that fills the 128 MiB of a TPU v5e's VMEM,
# the largest filter its compiler accepts (tests/test_tpu_compile.py)
PALLAS_BLOOM_MAX_BITS = 1 << 29

# backends whose ops are safe under a leading vmapped lane axis (the
# multi-lane engine in ``core.batch``).  jax ops vmap trivially; the pallas
# kernels batch through pallas_call's batching rule, which lifts the lane
# axis into the grid — pinned bit-for-bit by tests/test_batch.py.  A future
# backend whose kernels lack a batching rule must be left out of this set
# so ``validate(lanes=...)`` rejects it at entry instead of mid-trace.
BATCHED_BACKENDS: Tuple[str, ...] = ("jax", "pallas")


def default_schedule(backend: str) -> str:
    """The closure schedule a backend runs when the caller names none:
    the static "doubling" baked into the pallas kernels, the
    early-exiting "while" loop of the jax reference ops."""
    return "doubling" if backend == "pallas" else "while"


class BackendCapabilityError(ValueError):
    """An op/backend/flag combination the registry cannot dispatch."""


@dataclasses.dataclass(frozen=True)
class OpSpec:
    name: str
    doc: str
    loaders: Dict[str, Callable[[], Callable]]

    def resolve(self, backend: str) -> Callable:
        if backend not in self.loaders:
            have = ", ".join(sorted(self.loaders))
            raise BackendCapabilityError(
                f"op {self.name!r} has no {backend!r} implementation "
                f"(available backends: {have}). {self.doc}")
        return self.loaders[backend]()


_OPS: Dict[str, OpSpec] = {}


def _register(name: str, doc: str, **loaders) -> None:
    _OPS[name] = OpSpec(name=name, doc=doc, loaders=loaders)


def get_op(name: str, backend: str) -> Callable:
    """Resolve an op implementation; raises BackendCapabilityError with the
    available alternatives instead of crashing mid-jit."""
    if backend not in BACKENDS:
        raise BackendCapabilityError(
            f"unknown backend {backend!r}; known backends: "
            f"{', '.join(BACKENDS)}")
    if name not in _OPS:
        raise BackendCapabilityError(
            f"unknown op {name!r}; registered ops: "
            f"{', '.join(sorted(_OPS))}")
    return _OPS[name].resolve(backend)


def ops() -> Tuple[str, ...]:
    return tuple(sorted(_OPS))


def capability_table() -> Dict[str, Tuple[str, ...]]:
    """op name -> backends that implement it (for docs and tests)."""
    return {name: tuple(b for b in BACKENDS if b in spec.loaders)
            for name, spec in sorted(_OPS.items())}


def device_memory_budget(fraction: float = 0.5) -> Optional[int]:
    """Best-effort device memory available for frontier pools, in bytes.

    Reads the default device's allocator stats (populated on TPU/GPU;
    absent on the CPU backend) and hands ``fraction`` of the free bytes to
    the caller — the rest stays headroom for the adjacency/children
    tensors and XLA scratch.  Returns ``None`` when the platform exposes
    no stats (the CPU backend), which callers (``batch.plan_capacity``)
    treat as "state-space bound only"; a device query that fails raises.
    DESIGN.md §10.
    """
    import jax
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        return None
    free = max(0, int(limit) - int(stats.get("bytes_in_use", 0)))
    return int(free * fraction)


# JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
# points, else this fixed directory at the checkout root (the path is part
# of what a later run must find again, so it never depends on a temp
# name, a pid or the time)
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
CHECKOUT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    directory is set here; otherwise the cache goes to ``.jax_cache/`` at
    the checkout root.  Either way, source locations drop the checkout
    root: a Pallas kernel's serialized body keeps them in the cache key,
    so without this a checkout at another path misses every entry its
    Pallas programs wrote.  Call once from an entry point, before the
    first compile.  Returns the directory in use."""
    import jax
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(str(CHECKOUT_ROOT) + os.sep))
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def validate(backend: str, *, mode: str = "sort",
             schedule: str = "doubling", use_mmw: bool = False,
             use_simplicial: bool = False,
             m_bits: Optional[int] = None, lanes: int = 1,
             shards: int = 1) -> None:
    """Fail fast on solver configurations the backend cannot run.

    Called at every entry point (``solver.decide``, ``ladder.solve``,
    ``distributed.decide_distributed``, ``batch.decide_lanes``, the CLI) so
    an unsupported combo surfaces as one actionable error before any
    tracing starts.  ``lanes > 1`` and ``shards > 1`` additionally require
    the backend's ops to be vmap-safe (``BATCHED_BACKENDS``) — the
    multi-lane engine vmaps whole decide loops, the sharded engine
    (``core.shard``) vmaps the per-shard expand/dedup pipeline.
    """
    if backend not in BACKENDS:
        raise BackendCapabilityError(
            f"unknown backend {backend!r}; known backends: "
            f"{', '.join(BACKENDS)}")
    if lanes < 1:
        raise BackendCapabilityError(
            f"lanes must be >= 1 (got {lanes})")
    if lanes > 1 and backend not in BATCHED_BACKENDS:
        raise BackendCapabilityError(
            f"backend {backend!r} does not support the multi-lane engine "
            f"(batched backends: {', '.join(BATCHED_BACKENDS)}); run with "
            "lanes=1 or switch backend.")
    if shards < 1:
        raise BackendCapabilityError(
            f"shards must be >= 1 (got {shards})")
    if shards > 1 and backend not in BATCHED_BACKENDS:
        raise BackendCapabilityError(
            f"backend {backend!r} does not support the sharded engine "
            f"(batched backends: {', '.join(BATCHED_BACKENDS)}); run with "
            "shards=1 or switch backend.")
    if mode not in DEDUP_MODES:
        raise BackendCapabilityError(
            f"unknown dedup mode {mode!r}; known modes: "
            f"{', '.join(DEDUP_MODES)}")
    schedules = PALLAS_SCHEDULES if backend == "pallas" else JAX_SCHEDULES
    if schedule not in schedules:
        raise BackendCapabilityError(
            f"backend={backend!r} does not implement schedule="
            f"{schedule!r} (supported: {', '.join(schedules)}). The pallas "
            "wavefront kernel has one fixed closure algorithm and accepts "
            "only the default schedule name; the alternative schedules "
            "exist only as jax reference loops. Use schedule='doubling' or "
            "backend='jax'.")
    if mode == "bloom" and backend == "pallas" \
            and m_bits is not None and m_bits % 32:
        raise BackendCapabilityError(
            f"backend='pallas' keeps the Bloom filter bit-packed in uint32 "
            f"words, so m_bits must be a multiple of 32 (got {m_bits}). "
            "Round m_bits up or use backend='jax'.")
    if mode == "bloom" and backend == "pallas" \
            and m_bits is not None and m_bits > PALLAS_BLOOM_MAX_BITS:
        raise BackendCapabilityError(
            f"backend='pallas' holds the Bloom filter in VMEM, which fits at "
            f"most m_bits={PALLAS_BLOOM_MAX_BITS} (got {m_bits}). Lower "
            "m_bits or use backend='jax'.")
    # pruning-rule coverage: both rules ride inside the fused pallas
    # wavefront kernel, so nothing to reject here — but resolving the op
    # now turns a future capability regression into an import-time error
    get_op("wavefront_expand", backend)
    if use_mmw:
        get_op("mmw_bound", backend)
    if use_simplicial and backend == "jax":
        # under pallas the rule exists only fused inside wavefront_expand
        get_op("simplicial_mask", "jax")


# ------------------------------------------------------------ registrations
#
# Loader thunks so that importing this module stays cheap and jax-only runs
# never touch jax.experimental.pallas.

def _jax_wavefront_expand():
    from . import expand
    return expand.wavefront_expand


def _pallas_wavefront_expand():
    from repro.kernels.wavefront import wavefront_expand
    return wavefront_expand


def _jax_expand_degrees():
    import jax as _jax
    from . import components

    def expand_degrees(adj, states, *, n, schedule="doubling"):
        deg, _reach = _jax.vmap(
            lambda s: components.eliminated_degrees(adj, s, n,
                                                    schedule=schedule))(states)
        return deg
    return expand_degrees


def _pallas_expand_degrees():
    from repro.kernels.expand import expand_degrees

    def expand_degrees_op(adj, states, *, n, schedule="doubling"):
        del schedule          # the kernel has one fixed closure algorithm
        return expand_degrees(adj, states, n=n)
    return expand_degrees_op


def _jax_mmw_bound():
    import jax as _jax
    from . import mmw as mmw_lib

    def mmw_bounds(reach, states, k, *, n):
        return _jax.vmap(
            lambda r, s: mmw_lib.mmw_bound(r, s, k, n))(reach, states)
    return mmw_bounds


def _pallas_mmw_bound():
    from repro.kernels.mmw import mmw_bounds

    def mmw_bounds_op(reach, states, k, *, n):
        return mmw_bounds(reach, states, k, n=n)
    return mmw_bounds_op


def _jax_simplicial_mask():
    from . import expand
    return expand.simplicial_mask


def _sort_dedup():
    from . import dedup

    def sort_dedup(flat, mask):
        skeys, svalid = dedup.sort_states(flat, mask)
        keep = dedup.unique_mask(skeys, svalid)
        return skeys, keep
    return sort_dedup


def _jax_bloom_query_insert():
    from . import bloom

    def query_insert(filt, keys, keep, *, m_bits, k_hashes):
        return bloom.query_and_insert(filt, keys, keep, m_bits, k_hashes)
    return query_insert


def _pallas_bloom_query_insert():
    from repro.kernels.bloom import bloom_insert

    def query_insert(filt, keys, keep, *, m_bits, k_hashes):
        return bloom_insert(filt, keys, keep, m_bits=m_bits,
                            k_hashes=k_hashes)
    return query_insert


def _jax_bloom_make_filter():
    from . import bloom

    def make_filter(m_bits):
        return bloom.make_filter(m_bits if m_bits is not None else 1)
    return make_filter


def _pallas_bloom_make_filter():
    from repro.kernels.bloom import make_filter_words

    def make_filter(m_bits):
        return make_filter_words(m_bits if m_bits is not None else 32)
    return make_filter


_register(
    "wavefront_expand",
    "The fused Listing-1 inner loop: expand + feasibility + simplicial "
    "collapse + MMW prune -> (children, feasible).",
    jax=_jax_wavefront_expand, pallas=_pallas_wavefront_expand)
_register(
    "expand_degrees",
    "deg_S(v) only (no reach / children) — benchmark & test surface for "
    "the unfused expansion kernel.",
    jax=_jax_expand_degrees, pallas=_pallas_expand_degrees)
_register(
    "mmw_bound",
    "Batched minor-min-width lower bounds from precomputed reach rows.",
    jax=_jax_mmw_bound, pallas=_pallas_mmw_bound)
_register(
    "simplicial_mask",
    "Standalone simplicial-candidate mask. The pallas form exists only "
    "fused inside wavefront_expand (it needs the VMEM-resident reach "
    "tiles); use backend='jax' or the fused op.",
    jax=_jax_simplicial_mask)
_register(
    "sort_dedup",
    "Exact lexicographic sort + first-occurrence mask. Registered for "
    "both backends as the same XLA sort: TPU sorting is XLA-native and a "
    "hand-rolled pallas sort would be slower (DESIGN.md §3).",
    jax=_sort_dedup, pallas=_sort_dedup)
_register(
    "bloom_query_insert",
    "Bloom-filter query-and-insert. jax: masked scatter-max on a "
    "byte-per-bit filter; pallas: bit-packed filter with sequential-grid "
    "atomic-OR semantics. Identical was_new bits for intra-batch-unique "
    "inputs (guaranteed by the preceding sort_dedup).",
    jax=_jax_bloom_query_insert, pallas=_pallas_bloom_query_insert)
_register(
    "bloom_make_filter",
    "Backend-matched empty Bloom filter (pass m_bits=None for the dummy "
    "carried through sort-mode loops).",
    jax=_jax_bloom_make_filter, pallas=_pallas_bloom_make_filter)
