"""Backend registry: dispatch, capability table, fail-fast validation.

The contract under test (ISSUE 2): every wavefront op resolves through one
registry; unsupported op/backend/flag combinations raise
``BackendCapabilityError`` at entry — at ``get_op``, ``validate``,
``solver.decide``/``solve`` and the CLI — never a bare TypeError deep
inside a jit.
"""
import os

import pytest

from repro.core import backend as backend_lib
from repro.core import graph, ladder, solver
from repro.core.backend import BackendCapabilityError


# ------------------------------------------------------------------ get_op

def test_every_registered_op_resolves_to_a_callable():
    for op, backends in backend_lib.capability_table().items():
        for b in backends:
            assert callable(backend_lib.get_op(op, b)), (op, b)


def test_unknown_backend_rejected():
    with pytest.raises(BackendCapabilityError, match="unknown backend"):
        backend_lib.get_op("wavefront_expand", "cuda")


def test_unknown_op_rejected_with_op_listing():
    with pytest.raises(BackendCapabilityError, match="wavefront_expand"):
        backend_lib.get_op("warp_speed", "jax")


def test_missing_impl_names_available_backends():
    # simplicial_mask exists standalone only in jax (the pallas form is
    # fused inside wavefront_expand)
    with pytest.raises(BackendCapabilityError, match="jax"):
        backend_lib.get_op("simplicial_mask", "pallas")


def test_capability_table_shape():
    table = backend_lib.capability_table()
    assert table["wavefront_expand"] == ("jax", "pallas")
    assert table["sort_dedup"] == ("jax", "pallas")
    assert table["bloom_query_insert"] == ("jax", "pallas")
    assert table["simplicial_mask"] == ("jax",)


# ---------------------------------------------------------------- validate

def test_validate_accepts_full_pallas_feature_set():
    backend_lib.validate("pallas", mode="bloom", schedule="doubling",
                         use_mmw=True, use_simplicial=True, m_bits=1 << 14)


@pytest.mark.parametrize("schedule", ["while", "linear", "matmul"])
def test_pallas_rejects_jax_only_schedules(schedule):
    with pytest.raises(BackendCapabilityError, match="doubling"):
        backend_lib.validate("pallas", schedule=schedule)


def test_pallas_bloom_requires_word_aligned_filter():
    with pytest.raises(BackendCapabilityError, match="multiple of 32"):
        backend_lib.validate("pallas", mode="bloom", m_bits=(1 << 14) + 1)
    # jax byte-per-bit filter has no such constraint
    backend_lib.validate("jax", mode="bloom", m_bits=(1 << 14) + 1)


def test_pallas_bloom_filter_must_fit_vmem():
    top = backend_lib.PALLAS_BLOOM_MAX_BITS
    backend_lib.validate("pallas", mode="bloom", m_bits=top)
    with pytest.raises(BackendCapabilityError, match="VMEM"):
        backend_lib.validate("pallas", mode="bloom", m_bits=2 * top)
    # the jax filter lives in HBM; sort mode ignores m_bits
    backend_lib.validate("jax", mode="bloom", m_bits=2 * top)
    backend_lib.validate("pallas", mode="sort", m_bits=2 * top)


def test_validate_rejects_unknown_mode_and_backend():
    with pytest.raises(BackendCapabilityError, match="mode"):
        backend_lib.validate("jax", mode="hashset")
    with pytest.raises(BackendCapabilityError, match="backend"):
        backend_lib.validate("tpu-native")


# ------------------------------------------------- entry-point enforcement

def test_solver_entry_points_fail_fast():
    g = graph.petersen()
    kw = dict(cap=1 << 8, block=32, mode="sort", use_mmw=False,
              m_bits=1 << 10, k_hashes=4)
    with pytest.raises(BackendCapabilityError):
        solver.decide(g, 3, [], schedule="while", backend="pallas", **kw)
    with pytest.raises(BackendCapabilityError):
        ladder.solve(g, cap=1 << 8, block=32, backend="pallas",
                     schedule="linear")
    with pytest.raises(BackendCapabilityError):
        ladder.solve(g, cap=1 << 8, block=32, backend="opencl")


def test_solve_schedule_default_is_backend_aware():
    """schedule=None resolves per backend, so the pallas default just works
    instead of tripping over the jax-only 'while' schedule."""
    g = graph.petersen()
    a = ladder.solve(g, cap=1 << 10, block=32, backend="jax")
    b = ladder.solve(g, cap=1 << 10, block=32, backend="pallas")
    assert a.width == b.width == 4


def test_cli_reports_capability_error(capsys):
    from repro.launch import solve as cli
    rc = cli.main(["--graph", "petersen", "--backend", "pallas",
                   "--schedule", "while"])
    assert rc == 2
    assert "unsupported configuration" in capsys.readouterr().err


@pytest.fixture
def source_regex():
    """Restore JAX's source-path canonicalization after the test."""
    import jax
    name = "jax_hlo_source_file_canonicalization_regex"
    before = getattr(jax.config, name)
    yield
    jax.config.update(name, before)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             source_regex):
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(backend_lib.CACHE_ENV, str(tmp_path))
    assert backend_lib.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_keys_drop_the_checkout_path(monkeypatch, tmp_path,
                                                   source_regex):
    """A program traced from this checkout names its source files
    relative to the checkout root, so its cache key is the same wherever
    the checkout lives."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv(backend_lib.CACHE_ENV, str(tmp_path))
    root = str(backend_lib.CHECKOUT_ROOT) + os.sep

    def traced_here(x):
        return jnp.sin(x) + 1

    def locations():
        return jax.jit(traced_here).lower(jnp.ones(4)).as_text(
            debug_info=True)

    assert root + "tests" in locations()
    backend_lib.enable_compile_cache()
    text = locations()
    assert root not in text
    assert '"tests' + os.sep + "test_backend_registry.py" in text


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      source_regex):
    import os
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.delenv(backend_lib.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = backend_lib.enable_compile_cache()
        assert backend_lib.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_device_memory_budget_is_none_without_stats():
    # the CPU backend reports no allocator stats: "state-space bound only"
    assert backend_lib.device_memory_budget() is None
