"""The one traffic generator: turns a traffic mix (``bench/traffic/<mix>.json``)
and a configuration's instance family into the requests of one run.

Every draw comes from ``--seed``, through streams of their own (``stream``),
so the warm-up, the timed requests and the sample that the reference
checks never share random numbers.  Every seed gives a run the same
amount of work: the same number of arrivals, the same multiset of gaps
between them and the same sizes; the seed changes only their order, the
graphs drawn and their labels.

Mix keys:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when its last one returns) or ``"open"`` (arrivals on a
  schedule at ``rate_hz``, whatever the service does);
- ``arrivals``: ``"poisson"`` — the gaps are the M quantiles of an
  exponential distribution with mean 1 / ``rate_hz``, shuffled, where
  M = round(``rate_hz`` x seconds);
- ``instances`` (optional): narrows the configuration's instance lists to
  a subset of them, key by key (``{"names": [...]}``), as a mix of short
  prompts draws on part of what a model serves.
"""
from __future__ import annotations

import importlib
import math

import numpy as np

# stream ids under one --seed
TIMED, GAPS, WARMUP, SAMPLE = range(4)


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), which]))


def family(name: str):
    """An instance family: ``bench/instances/<name>.py``."""
    return importlib.import_module(f"bench.instances.{name}")


def poisson_offsets(rate_hz: float, seconds: float, rng) -> list:
    """Due times (seconds from the window's start) of round(rate x seconds)
    arrivals whose gaps are the exponential quantiles, shuffled."""
    m = int(round(rate_hz * seconds))
    if m < 1:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / m) / rate_hz for i in range(m)]
    gaps = [gaps[int(i)] for i in rng.permutation(m)]
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g
    return out


def instances(config: dict, traffic: dict) -> dict:
    """The instance parameters of a run: the configuration's, each list
    narrowed to the mix's own, where the mix has one."""
    params = dict(config["instances"])
    for key, sub in traffic.get("instances", {}).items():
        extra = sorted(set(sub) - set(params[key]))
        if extra:
            raise ValueError(f"mix asks for {key} {extra}, which the "
                             f"configuration does not have")
        params[key] = list(sub)
    return params


def warmup(config: dict, traffic: dict, seed: int) -> list:
    """The instances that set-up sends: the family's own warm-up set,
    largest first, so the first round fixes the pool's program shape."""
    fam = family(config["family"])
    insts = fam.warmup(instances(config, traffic), stream(seed, WARMUP))
    return sorted(insts, key=lambda r: -r["n"])


def closed_source(config: dict, traffic: dict, seed: int):
    """Endless requests for a closed loop, drawn in batches in a fixed
    order, so a run sees the same sequence whatever its pace."""
    fam = family(config["family"])
    params, rng = instances(config, traffic), stream(seed, TIMED)
    while True:
        yield from fam.build(params, rng, 64)


def open_plan(config: dict, traffic: dict, seed: int, seconds: float):
    """The arrivals of an open loop: a list of (due offset, request)."""
    fam = family(config["family"])
    offsets = poisson_offsets(float(traffic["rate_hz"]), seconds,
                              stream(seed, GAPS))
    reqs = fam.build(instances(config, traffic), stream(seed, TIMED),
                     len(offsets))
    return list(zip(offsets, reqs))
