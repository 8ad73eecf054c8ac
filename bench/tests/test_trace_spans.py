"""The attribution of a trace's device-idle time to the program's ``tw.*``
host spans (``bench/trace_spans.py``), on a hand-made trace shaped as the
TPU profiler writes one, whose numbers are worked out below; and the
readers of the per-layer metrics over the program's spans and counters.

Run from the checkout root:  python -m pytest bench/tests
"""
from types import SimpleNamespace as NS

import pytest

from bench import run
from bench import trace_reduce as tr
from bench import trace_spans as ts

KERNEL = ("%wavefront_pallas.13 = s32[8,64,16,128]{3,2,1,0:T(8,128)} "
          "custom-call(%a, %k, %al, %s, %v), "
          "custom_call_target=\"tpu_custom_call\"")


def ev(name, start, dur, hlo=None):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=[("long_name", hlo)] if hlo else [])


def hand_trace():
    """Window 1000..11000 ns.  Device ops: a sort 2000-3000, the kernel
    3000-5000, two fusions 7000-8500, and a copy 10500-12000, which the
    window cuts at 11000.  Busy: [2000, 5000] + [7000, 8500] +
    [10500, 11000] = 5000 ns; idle: [1000, 2000], [5000, 7000],
    [8500, 10500] = 5000 ns.

    The driver line: a step 1000-6000 (launch 1100-1800 holding an admit
    1200-1500; sync 2000-5800 holding a wait 2100-5500 and a feed
    5500-5800), an idle wait 6000-9000, and a step 9000-10800 (launch
    9000-9500 holding an enqueue 9100-9400; sync 9600-10800 holding a
    wait 9600-10800).  A client line holds bench.await over the whole
    window, and a stray tw.admit that is not the driver's."""
    device = NS(name="/device:TPU:0", stats=[], lines=[
        NS(name="XLA Modules", events=[ev("jit_f", 0, 20000)]),
        NS(name="XLA Ops", events=[
            ev("sort.3", 2000, 1000), ev("wavefront_pallas.13", 3000, 2000,
                                         KERNEL),
            ev("fusion.1", 7000, 1000), ev("fusion.2", 8000, 500),
            ev("copy.2", 10500, 1500), ev("early", 100, 200)])])
    driver = NS(name="twserved-driver", events=[
        ev("tw.step", 1000, 5000), ev("tw.launch", 1100, 700),
        ev("tw.admit", 1200, 300), ev("tw.sync", 2000, 3800),
        ev("tw.wait", 2100, 3400), ev("tw.feed", 5500, 300),
        ev("tw.idle", 6000, 3000), ev("tw.step", 9000, 1800),
        ev("tw.launch", 9000, 500), ev("tw.enqueue", 9100, 300),
        ev("tw.sync", 9600, 1200), ev("tw.wait", 9600, 1200)])
    client = NS(name="client", events=[ev("bench.await", 1000, 10000),
                                       ev("tw.admit", 5000, 2000)])
    host = NS(name="/host:CPU", stats=[], lines=[
        NS(name="python", events=[ev("bench.window", 1000, 10000)]),
        client, driver])
    return [host, device]


def test_idle_goes_to_the_innermost_driver_span():
    out = ts.idle_by_span(hand_trace())
    assert out == pytest.approx({
        "tw.step": 600e-9, "tw.launch": 600e-9, "tw.admit": 300e-9,
        "tw.wait": 1400e-9, "tw.feed": 300e-9, "tw.idle": 1500e-9,
        "tw.enqueue": 300e-9, "none": 0.0})
    busy = tr.reduce_planes(hand_trace())["busy_s"]
    assert sum(out.values()) == pytest.approx(10000e-9 - busy)


def test_idle_without_driver_spans_is_unattributed():
    host, device = hand_trace()
    host.lines.pop()
    assert ts.idle_by_span([host, device]) == pytest.approx(
        {"none": 5000e-9})


def test_nested_spans_cut_at_their_parent():
    tiles = ts._tiles([(0, 10, "a"), (2, 12, "b"), (4, 6, "c")])
    assert tiles == [(0, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "b")]


def test_planes_iterated_once_are_enough():
    """``ProfileData.planes`` can be iterated only once."""
    assert ts.idle_by_span(iter(hand_trace())) == \
        pytest.approx(ts.idle_by_span(hand_trace()))


# ------------------------------------------------------------- the readers

def pools(t0=None, t1=None, c0=None, c1=None):
    return {"pool0": {"timings": t0 or {}, "counters": c0 or {}},
            "pool1": {"timings": t1 or {}, "counters": c1 or {}}}


def timing(calls, total_s):
    return {"calls": calls, "total_s": total_s, "max_s": total_s}


def test_admit_ms():
    read = run.metric_reader("admit_ms")
    rec = pools({"tw.admit": timing(2, 0.5)}, {"tw.admit": timing(6, 0.9)})
    assert read(rec) == pytest.approx(100.0)
    assert read(pools({"tw.admit": timing(2, 0.5)},
                      {"tw.admit": timing(2, 0.5)})) is None
    assert read(pools()) is None


def test_host_round_ms():
    read = run.metric_reader("host_round_ms")
    rec = pools({"tw.step": timing(3, 1.0), "tw.wait": timing(3, 0.5),
                 "tw.sync": timing(3, 0.7)},
                {"tw.step": timing(7, 5.0), "tw.wait": timing(7, 3.5),
                 "tw.sync": timing(5, 4.0)})
    # (4.0 - 3.0) s of host time over 2 syncs
    assert read(rec) == pytest.approx(500.0)
    assert read(pools({"tw.step": timing(1, 1.0)},
                      {"tw.step": timing(2, 2.0)})) is None
    assert read(pools()) is None


@pytest.mark.parametrize("name,num,den", [("lane_fill", "lanes_decided",
                                           "lane_slots"),
                                          ("row_fill", "lane_expanded",
                                           "lane_row_slots")])
def test_fill_shares(name, num, den):
    read = run.metric_reader(name)
    rec = pools(c0={num: 10, den: 40}, c1={num: 25, den: 100})
    assert read(rec) == pytest.approx(25.0)
    assert read(pools(c0={num: 10, den: 40}, c1={num: 10, den: 40})) \
        is None
    assert read(pools(c0={"lanes_decided": 3}, c1={"lanes_decided": 9})) \
        is None
