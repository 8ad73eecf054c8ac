"""The ``table1-wide`` configuration and its cell, on CPU: the weighted
instance list, one word per state, and the two refill readers.

Run from the checkout root:  python -m pytest bench/tests
"""
import numpy as np
import pytest

from bench import arrivals, run
from bench.instances import table1

CELL = "table1-wide.closed8"


@pytest.fixture(scope="module")
def loaded():
    return {trace: run.load_cell(CELL, trace) for trace in (False, True)}


def test_mix_sends_two_mcgee_in_every_seven(loaded):
    """Requests walk the weighted list in rounds of seven, each with McGee
    twice; the closed loop draws them 64 at a time."""
    config, traffic = loaded[False]["config"], loaded[False]["traffic"]
    params = arrivals.instances(config, traffic)
    reqs = table1.build(params, np.random.default_rng(3), 7 * 12)
    for i in range(0, len(reqs), 7):
        names = sorted(r["key"] for r in reqs[i:i + 7])
        assert names == sorted(config["instances"]["names"])
        assert names.count("mcgee") == 2
    assert all(r["width"] == table1.PUBLISHED[r["key"]] for r in reqs)
    source = arrivals.closed_source(config, traffic, 2 ** 31 + 77)
    first = [next(source)["key"] for _ in range(64)]
    assert first.count("mcgee") in (18, 19)


def test_every_instance_needs_one_word(loaded):
    config = loaded[False]["config"]
    for name in config["instances"]["names"]:
        n, _edges = table1.GENERATORS[name]()
        assert n <= 32, name
    warm = arrivals.warmup(config, loaded[False]["traffic"], 5)
    assert len(warm) == 7 and warm[0]["n"] == 25


def test_cell_reports_its_metrics(loaded):
    assert {m["name"] for m in loaded[False]["metrics"]} == \
        {"solves_per_s", "setup_s"}
    assert {"refills_per_level", "append_per_kept"} <= \
        {m["name"] for m in loaded[True]["metrics"]}
    assert loaded[False]["config"]["frontier_cap"] == 1 << 20


def _rec(c0, c1):
    return {"pool0": {"counters": c0}, "pool1": {"counters": c1}}


@pytest.mark.parametrize("name,counters,want", [
    ("refills_per_level", ("refills", "lane_levels"), 12 / 40),
    ("append_per_kept", ("appended_rows", "lane_expanded"), 12 / 40)])
def test_refill_readers(name, counters, want):
    read = run.metric_reader(name)
    top, bottom = counters
    got = read(_rec({top: 3, bottom: 10}, {top: 15, bottom: 50}))
    assert np.isclose(got, want)
    # a program without the counter, as before the refill: no reading
    assert read(_rec({bottom: 10}, {bottom: 50})) is None
    assert read(_rec({top: 3, bottom: 10}, {top: 3, bottom: 10})) is None
