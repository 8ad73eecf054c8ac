"""The trace reduction, on a hand-made trace shaped as the TPU profiler
writes one (``/device:TPU:0`` plane, ``XLA Ops`` line, op names from the
compiled program), whose numbers are worked out below.

Run from the checkout root:  python -m pytest bench/tests
"""
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr

KERNEL = ("%wavefront_pallas.13 = s32[8,64,16,128]{3,2,1,0:T(8,128)} "
          "custom-call(%a, %k, %al, %s, %v), "
          "custom_call_target=\"tpu_custom_call\"")


def ev(name, start, dur, hlo=None):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=[("long_name", hlo)] if hlo else [])


def hand_trace():
    """Window 1000..11000 ns.  Device ops: a sort 2000-4000, the kernel
    3000-5000 (overlapping the sort), a fusion 7000-8000, and an op
    10500-12000 that the window cuts at 11000.  Busy: union
    [2000, 5000] + [7000, 8000] + [10500, 11000] = 4500 ns."""
    device = NS(name="/device:TPU:0", stats=[], lines=[
        NS(name="XLA Modules", events=[ev("jit_f", 0, 20000)]),
        NS(name="XLA Ops", events=[
            ev("sort.3", 2000, 2000, "%sort.3 = (u32[4]) sort(u32[4] %x)"),
            ev("wavefront_pallas.13", 3000, 2000, KERNEL),
            ev("fusion.1", 7000, 1000, "%fusion.1 = u32[4] fusion()"),
            ev("copy.2", 10500, 1500, "%copy.2 = u32[4] copy()"),
            ev("early", 100, 200, "%early = u32[4] copy()")])])
    host = NS(name="/host:CPU", stats=[], lines=[
        NS(name="python", events=[ev("bench.window", 1000, 10000),
                                  ev("bench.await", 1200, 9000),
                                  ev("bench.submit", 5200, 1700)])])
    return [host, device]


def test_hand_trace():
    out = tr.reduce_planes(hand_trace())
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx(4500e-9)
    assert out["sort_s"] == pytest.approx(2000e-9)
    ops = dict((name, s) for name, s in out["device_ops"])
    assert ops == pytest.approx({"sort.3": 2000e-9,
                                 "wavefront_pallas.13": 2000e-9,
                                 "fusion.1": 1000e-9, "copy.2": 500e-9})
    # gaps: [1000,2000], [5000,7000], [8000,10500]
    assert [s for _n, s in out["idle_gaps"]] == pytest.approx(
        [2500e-9, 2000e-9, 1000e-9])
    # [5000,7000]: bench.submit covers 1700 ns of it, bench.await 2000
    assert [n for n, _s in out["idle_gaps"]] == ["bench.await"] * 3
    w = out["wavefront"]
    assert w["calls"] == 1 and w["s"] == pytest.approx(2000e-9)
    # per lane: adjacency 64*2 + k 1 + allowed 2 + states 2048*2
    # + valid 2048 + feasibility 64*2048 words, 4 bytes each, 8 lanes
    assert w["bytes"] == 4 * 8 * (128 + 1 + 2 + 4096 + 2048 + 131072)


def test_nested_ops_count_their_own_time():
    """A while op whose span holds its body's ops: busy is the union, and
    each op's time is its own."""
    host, device = hand_trace()
    device.lines[1].events = [
        ev("while.1", 2000, 6000, "%while.1 = (u32[4]) while()"),
        ev("sort.3", 2500, 1000, "%sort.3 = (u32[4]) sort(u32[4] %x)"),
        ev("fusion.1", 4000, 500, "%fusion.1 = u32[4] fusion()"),
        ev("wavefront_pallas.13", 5000, 2000, KERNEL)]
    out = tr.reduce_planes([host, device])
    assert out["busy_s"] == pytest.approx(6000e-9)
    assert out["sort_s"] == pytest.approx(1000e-9)
    assert dict((n, s) for n, s in out["device_ops"]) == pytest.approx(
        {"while.1": 2500e-9, "sort.3": 1000e-9, "fusion.1": 500e-9,
         "wavefront_pallas.13": 2000e-9})
    assert out["wavefront"]["s"] == pytest.approx(2000e-9)


def test_kernel_bytes():
    assert tr.wavefront_bytes(1, 32, 1) == 4 * (32 + 1 + 1 + 128 + 128
                                                + 32 * 128)
    assert tr.kernel_shape(ev("wavefront_pallas.2", 0, 1, KERNEL)) == \
        (8, 64, 16)


def test_missing_window_span_is_an_error():
    host, device = hand_trace()
    host.lines[0].events.pop(0)
    with pytest.raises(RuntimeError):
        tr.reduce_planes([host, device])
