"""The main-path Pallas kernels compile natively for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described topology.
Interpret mode hides what the chip's compiler refuses (tile shapes, scalar
stores to VMEM, gathers), so these tests pass ``interpret=False`` and
check that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every pytest-xdist worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry compiled for a described chip cannot be read back
here).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bitset
from repro.core.backend import PALLAS_BLOOM_MAX_BITS
from repro.kernels.bloom.kernel import bloom_insert_pallas
from repro.kernels.wavefront.kernel import wavefront_pallas

ROWS = 2048                # one chunk of the service's default block
LANES = 8                  # the service's default lane pool
M_BITS = 1 << 24           # the service's default Bloom filter


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache):
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _wavefront_shapes(n, lead=()):
    w = bitset.n_words(n)
    return [(lead + (n, w), jnp.uint32), (lead + (ROWS, w), jnp.uint32),
            (lead + (ROWS,), jnp.bool_), (lead + (1, 1), jnp.int32),
            (lead + (w,), jnp.uint32)]


@pytest.mark.parametrize("use_mmw,use_simplicial",
                         [(False, False), (True, False), (False, True),
                          (True, True)])
@pytest.mark.parametrize("n", [36, 64])
def test_wavefront_compiles_native(one_chip, n, use_mmw, use_simplicial):
    def fn(adj, states, valid, k, allowed):
        return wavefront_pallas(adj, states, valid, k, allowed, n=n,
                                use_mmw=use_mmw,
                                use_simplicial=use_simplicial,
                                interpret=False)
    text = _compiled_text(fn, one_chip, *_wavefront_shapes(n))
    assert "tpu_custom_call" in text


def test_wavefront_compiles_native_under_lane_vmap(one_chip):
    """The serving pool reaches the kernel through ``jax.vmap`` over its
    lanes (``core.batch``): the batching rule lifts the lane axis into
    the grid, and the lifted blocks must still tile."""
    n = 36

    def fn(adj, states, valid, k, allowed):
        return jax.vmap(lambda *a: wavefront_pallas(
            *a, n=n, interpret=False))(adj, states, valid, k, allowed)
    text = _compiled_text(fn, one_chip, *_wavefront_shapes(n, (LANES,)))
    assert "tpu_custom_call" in text


def test_bloom_compiles_native(one_chip):
    rows = ROWS * 36                        # one chunk's children at n=36

    def fn(filt, states, valid):
        return bloom_insert_pallas(filt, states, valid, m_bits=M_BITS,
                                   interpret=False)
    text = _compiled_text(fn, one_chip, ((M_BITS // 32,), jnp.uint32),
                          ((rows, 2), jnp.uint32), ((rows,), jnp.bool_))
    assert "tpu_custom_call" in text


def _bloom_text(one_chip, m_bits, rows=4096):
    def fn(filt, states, valid):
        return bloom_insert_pallas(filt, states, valid, m_bits=m_bits,
                                   interpret=False)
    return _compiled_text(fn, one_chip, ((m_bits // 32,), jnp.uint32),
                          ((rows, 2), jnp.uint32), ((rows,), jnp.bool_))


def test_bloom_filter_bound_is_the_vmem_limit(one_chip):
    """``backend.validate`` refuses pallas Bloom filters above
    ``PALLAS_BLOOM_MAX_BITS``: that size still compiles, twice it runs the
    chip out of VMEM."""
    assert "tpu_custom_call" in _bloom_text(one_chip, PALLAS_BLOOM_MAX_BITS)
    with pytest.raises(Exception, match="vmem"):
        _bloom_text(one_chip, 2 * PALLAS_BLOOM_MAX_BITS)


def test_pool_program_keeps_the_kernel_name_under_scopes(one_chip,
                                                         monkeypatch):
    """The lane pool's program with the native kernel: the ``tw.*`` named
    scopes reach the kernel call's op metadata, while the call itself is
    still named ``wavefront_pallas.<i>`` — the name the benchmark's trace
    reduction finds the kernel by."""
    import re
    from repro.core import batch, frontier
    from repro.kernels.wavefront import ops as wavefront_ops
    monkeypatch.setattr(wavefront_ops, "default_interpret", lambda: False)
    lanes, n, cap = 2, 24, 2048           # shapes no other test traces
    w = bitset.n_words(n)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fr = frontier.Frontier(spec((lanes, cap, w), jnp.uint32),
                           spec((lanes,), jnp.int32),
                           spec((lanes,), jnp.int32))
    text = batch._lanes_decide.lower(
        spec((lanes, n, w), jnp.uint32), spec((lanes, w), jnp.uint32),
        spec((lanes,), jnp.int32), spec((lanes,), jnp.int32), fr, n=n,
        cap=cap, block=128, mode="sort", use_mmw=False, m_bits=1 << 24,
        k_hashes=4, schedule="doubling", backend="pallas",
        use_simplicial=False).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert calls
    for ln in calls:
        assert re.match(r"\s*(ROOT )?%wavefront_pallas\.\d+ = ", ln), ln
        assert "/tw.level/" in ln and "/tw.expand/" in ln
