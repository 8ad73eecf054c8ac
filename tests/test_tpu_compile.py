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
import numpy as np
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


def _index_shapes(text):
    """{(op, index operand shape)} of every scatter and gather in a
    compiled program's text."""
    import re
    shapes = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    return {(op, shapes[idx]) for op, idx in re.findall(
        r"= \S+ (scatter|gather)\(%[^,]+, %([^,)]+)", text)}


@pytest.mark.parametrize("chip", [False, True], ids=["cpu", "v5e"])
def test_lane_program_has_no_per_row_scatter(request, monkeypatch, chip):
    """The lane pool's program compacts rows by sort and window write:
    the scatters and gathers left in it take one index per lane, so
    their index shapes stay the same as ``block`` and ``cap`` grow.  A
    row-wise ``.at[idx].set`` in the append or the dedup would bring an
    index per row back.  On the CPU the program composes the jax ops (a
    separate trace from the chip's: the kernel's mode is read while
    tracing), for the described chip it holds the native kernel."""
    from repro.core import batch, frontier
    sharding, backend = None, "jax"
    if chip:
        backend = "pallas"
        from repro.kernels.wavefront import ops as wavefront_ops
        sharding = request.getfixturevalue("one_chip")
        monkeypatch.setattr(wavefront_ops, "default_interpret",
                            lambda: False)
    lanes, n = 2, 24
    w = bitset.n_words(n)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def index_shapes(cap, block):
        fr = frontier.Frontier(spec((lanes, cap, w), jnp.uint32),
                               spec((lanes,), jnp.int32),
                               spec((lanes,), jnp.int32))
        text = batch._lanes_decide.lower(
            spec((lanes, n, w), jnp.uint32), spec((lanes, w), jnp.uint32),
            spec((lanes,), jnp.int32), spec((lanes,), jnp.int32), fr, n=n,
            cap=cap, block=block, mode="sort", use_mmw=False,
            m_bits=1 << 24, k_hashes=4, schedule="doubling",
            backend=backend, use_simplicial=False).compile().as_text()
        return _index_shapes(text)

    sizes = [(2048, 256), (4096, 256), (4096, 512)]      # (cap, block)
    found = [index_shapes(cap, block) for cap, block in sizes]
    assert found[0], "the lane program lost its window slices"
    for got, (cap, block) in zip(found[1:], sizes[1:]):
        assert got == found[0], (cap, block, got, found[0])
    for _op, shape in found[0]:
        dims = [int(d) for d in shape[shape.index("[") + 1:-1].split(",")
                if d]
        assert np.prod(dims) < 256, shape       # per lane, not per row


def _computations(text):
    """{computation name: its instruction lines} of a program's text."""
    import re
    comps, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{\s*$", ln)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(ln)
    return comps


def _lane_program_text(request, monkeypatch, chip):
    """The lane pool's program, compiled on the CPU (jax ops) or for the
    described chip (native kernel), at a ``block`` above 128 so that it
    holds both level sweeps."""
    from repro.core import batch, frontier
    sharding, backend = None, "jax"
    if chip:
        backend = "pallas"
        from repro.kernels.wavefront import ops as wavefront_ops
        sharding = request.getfixturevalue("one_chip")
        monkeypatch.setattr(wavefront_ops, "default_interpret",
                            lambda: False)
    lanes, n, cap, block = 2, 24, 4096, 256
    w = bitset.n_words(n)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    fr = frontier.Frontier(spec((lanes, cap, w), jnp.uint32),
                           spec((lanes,), jnp.int32),
                           spec((lanes,), jnp.int32))
    return batch._lanes_decide.lower(
        spec((lanes, n, w), jnp.uint32), spec((lanes, w), jnp.uint32),
        spec((lanes,), jnp.int32), spec((lanes,), jnp.int32), fr, n=n,
        cap=cap, block=block, mode="sort", use_mmw=False, m_bits=1 << 24,
        k_hashes=4, schedule="doubling", backend=backend,
        use_simplicial=False).compile().as_text()


def _under_conditionals(comps):
    """Computations reached from a conditional's branches through calls,
    fusions and nested conditionals (a loop body inside a branch would
    not do)."""
    import re
    calls = r"calls|true_computation|false_computation"
    todo = []
    for lines in comps.values():
        for ln in lines:
            if " conditional(" in ln:
                todo += re.findall(
                    r"(?:true_computation|false_computation)=%([\w.\-]+)",
                    ln)
                for grp in re.findall(r"branch_computations=\{([^}]*)\}",
                                      ln):
                    todo += [x.strip().lstrip("%") for x in grp.split(",")]
    inside = set()
    while todo:
        c = todo.pop()
        if c in inside or c not in comps:
            continue
        inside.add(c)
        for ln in comps[c]:
            todo += re.findall(rf"(?:{calls})=%([\w.\-]+)", ln)
            for grp in re.findall(r"branch_computations=\{([^}]*)\}", ln):
                todo += [x.strip().lstrip("%") for x in grp.split(",")]
    return inside


@pytest.mark.parametrize("chip", [False, True], ids=["cpu", "v5e"])
def test_lane_program_refills_under_a_conditional(request, monkeypatch,
                                                  chip):
    """The mid-level refill's sorts sit in the branches of a conditional
    (reached through nothing but calls, fusions and conditionals), not in
    the chunk loop's body behind a ``select``: under the lane ``vmap``
    its predicate is one value for every lane, so the full-buffer sorts
    run only on the chunks where some lane needs them."""
    comps = _computations(_lane_program_text(request, monkeypatch, chip))
    inside = _under_conditionals(comps)
    refill_sorts = [(c, ln) for c, lines in comps.items() for ln in lines
                    if " sort(" in ln and "/tw.refill/" in ln]
    assert len(refill_sorts) >= 2, "the lane program lost its refill"
    for c, ln in refill_sorts:
        assert c in inside, (c, ln.strip()[:200])
