"""The harness end to end on CPU at a tiny size: a sound run is correct,
the control and a planted fault are not, and without a TPU the command
exits non-zero with no result.

Run from the checkout root:  python -m pytest bench/tests
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run

ROOT = run.ROOT
TINY = {"family": "table1",
        "instances": {"names": ["petersen", "myciel3"]},
        "pool": {"lanes": 2, "block": 32, "backend": "jax", "mode": "sort",
                 "cache": 0},
        "frontier_cap": 4096,
        "control": {"cap": 4}}
OPEN = {"loop": "open", "arrivals": "poisson", "rate_hz": 3.0}
CLOSED = {"loop": "closed", "clients": 2}
SEED = 2 ** 31 + 12345       # larger than 32 signed bits hold


def tiny(traffic, metrics=("setup_s", "solves_per_s", "dispatch_ms")):
    return {"cell": {"name": "tiny", "chips": 1}, "config": TINY,
            "traffic": traffic,
            "metrics": [{"name": m, "unit": "x"} for m in metrics]}


def cpu_run(traffic, **kw):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    return run.run_cell(tiny(traffic), SEED, 3.0, False, require_tpu=False,
                        processes=2, **kw)


@pytest.mark.parametrize("traffic", [OPEN, CLOSED], ids=["open", "closed"])
def test_sound_run_is_correct(traffic):
    out = cpu_run(traffic)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "solves_per_s", "dispatch_ms"}
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    """The control: every request with a frontier far below the stated
    one, which overflows and breaks the exactness the configuration
    states."""
    out = cpu_run(OPEN, knobs=TINY["control"])
    assert not out["correct"]
    assert out["checks"]["not_exact"]["value"] > 0


def test_altered_answer_is_not_correct(monkeypatch):
    """An answer altered where the scheduler produces it is caught."""
    from repro.serve.twscheduler import TwScheduler
    finish = TwScheduler._finish

    def wrong(self, req, inst):
        inst.result = dataclasses.replace(inst.result,
                                          width=inst.result.width + 1)
        return finish(self, req, inst)

    monkeypatch.setattr(TwScheduler, "_finish", wrong)
    out = cpu_run(OPEN)
    assert not out["correct"]
    assert out["checks"]["wrong_width"]["value"] > 0


@pytest.mark.parametrize("width", [5, 6], ids=["right", "wrong"])
def test_reference_checks_a_graph_with_no_published_width(width):
    """A request whose family gives no published width is checked against
    the plain reference (myciel3, relabelled: treewidth 5)."""
    from bench.instances import table1
    [req] = table1.build({"names": ["myciel3"]}, np.random.default_rng(5), 1)
    del req["width"]
    rec = {"req": req, "result": {"exact": True, "width": width}}
    got = run.check([rec], SEED, 1)
    assert got["reference_graphs"] == 1
    assert got["numbers"]["wrong_width"] == (width != 5)


def test_half_the_answers_left_out_is_not_correct(monkeypatch):
    """Every second request finished by the scheduler is never answered:
    the run waits its while past the close and counts it unanswered."""
    from repro.serve.twscheduler import TwScheduler
    finish = TwScheduler._finish

    def half(self, req, inst):
        if req.rid % 2 == 0:
            return finish(self, req, inst)

    monkeypatch.setattr(TwScheduler, "_finish", half)
    monkeypatch.setattr(run, "LATE_S", 2.0)
    out = cpu_run(OPEN)
    assert not out["correct"]
    assert out["checks"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("names,ok", [(["myciel3"], True),
                                      (["queen6_6"], False)],
                         ids=["subset", "not-in-configuration"])
def test_mix_narrows_the_instances(names, ok):
    from bench import arrivals
    mix = dict(OPEN, instances={"names": names})
    if not ok:
        with pytest.raises(ValueError):
            arrivals.open_plan(TINY, mix, SEED, 3.0)
        return
    plan = arrivals.open_plan(TINY, mix, SEED, 3.0)
    assert len(plan) == 9 and {r["key"] for _t, r in plan} == set(names)
    assert [r["key"] for r in arrivals.warmup(TINY, mix, SEED)] == names


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "table1-dimacs.closed8", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_existing_files():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in cells.values():
        assert w["config"] in configs and w["chips"] == 1
        assert os.path.isfile(os.path.join(
            run.BENCH, "traffic", w["traffic"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["per_layer"]:
        moved = {e["name"]: e for e in spec["end_to_end"]}[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
