#!/usr/bin/env python3
"""Smoke run of the treewidth solve service on a TPU.

    python chip_smoke.py               # one chip: the twserved solve path
    python chip_smoke.py --four-chips  # the distributed solver on 4 chips

One chip: starts a ``TwServer`` (the ``twserved`` front end) in this
process with the service defaults — 8 lanes, block 2048, auto frontier
capacity — and no result cache, so every request reaches the device.  The
paper's Table-1 instances go through a ``TwClient``; each width is checked
against tests/golden_widths.json, and queen5_5 against its known expanded
count.  The same requests then run on a second pool with
``backend="pallas"`` (native kernels), whose results must equal the jax
pool's, and whose compiled programs must hold the Pallas kernel.  Each pool
also answers one ``mode="bloom"`` request.  For each pool it prints every
compile and persistent-cache lookup (``jax.monitoring`` events), the
entries of the compile cache before and after, each pool program's
on-chip memory analysis, and ``peak_bytes_in_use``.

Four chips: ``ladder.solve_distributed`` over the mesh of all chips,
compared with a one-chip ``ladder.solve`` in the same process, with the
frontier shown to be spread over every chip.

Every check that fails exits non-zero before the last line is printed.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# heaviest first: its n and planned capacity fix the pool's one program
TABLE1 = ("queen6_6", "myciel4", "queen5_5", "desargues", "petersen")
HEAVY = "queen6_6"              # n=36, two-word states; exactness reported
BLOOM = "myciel4"
QUEEN5_5_EXPANDED = 2279        # sort mode, any backend or device
DISTRIBUTED = ("queen5_5", "myciel4")
DISTRIBUTED_CAP = 1 << 18       # the solve CLI's --distributed default


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def golden_widths() -> dict:
    with open(os.path.join(ROOT, "tests", "golden_widths.json")) as f:
        return {k: v["tw"] for k, v in json.load(f).items()
                if not k.startswith("_")}


# ---------------------------------------------------------------- one chip

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_WRITE = "/jax/compilation_cache/cache_misses"   # sent as it writes


class CompileLog:
    """Every XLA compile of the process, from ``jax.monitoring`` events:
    (program name, seconds, served from the persistent cache), plus the
    cache's lookups, hits and writes."""

    def __init__(self):
        import jax.monitoring as mon
        self.programs = []
        self.counts = {CACHE_LOOKUP: 0, CACHE_HIT: 0, CACHE_WRITE: 0}
        self._hit = False
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        if event in self.counts:
            self.counts[event] += 1
        self._hit = self._hit or event == CACHE_HIT

    def _on_duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE:
            self.programs.append((kw.get("fun_name", "?"), secs, self._hit))
            self._hit = False

    def mark(self):
        return len(self.programs), dict(self.counts)

    def summary(self, since) -> str:
        n0, c0 = since
        progs = self.programs[n0:]
        c = {k: v - c0[k] for k, v in self.counts.items()}
        slow = sorted(progs, key=lambda p: -p[1])[:3]
        return (f"{len(progs)} compiles, {sum(p[1] for p in progs):.3f}s; "
                f"cache lookups={c[CACHE_LOOKUP]} hits={c[CACHE_HIT]} "
                f"writes={c[CACHE_WRITE]}; slowest: "
                + ", ".join(f"{name} {secs:.3f}s "
                            f"{'hit' if hit else 'compiled'}"
                            for name, secs, hit in slow))


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class _ProgramRecorder:
    """Lowers every distinct program the pool's vmapped decide
    (``batch._lanes_decide``) runs, with the pool's own arguments, so each
    can be compiled again and inspected after the pool is done."""

    def __init__(self):
        from repro.core import batch
        self.batch = batch
        self.jitted = batch._lanes_decide
        self.calls = {}

    def __enter__(self):
        import jax

        def recording(*args, **kw):
            key = repr(([(a.shape, a.dtype) for a in jax.tree.leaves(args)],
                        sorted(kw.items())))
            if key not in self.calls:
                self.calls[key] = (self.jitted.lower(*args, **kw), kw)
            return self.jitted(*args, **kw)

        self.batch._lanes_decide = recording
        return self

    def __exit__(self, *exc):
        self.batch._lanes_decide = self.jitted

    def compiled(self, backend: str):
        """Compile each recorded program again (a persistent-cache hit
        when the pool's compile was written there); log its shape and
        on-chip memory analysis; return the compiled programs."""
        check(bool(self.calls), f"[{backend}] the pool never dispatched "
                                "its program")
        out = []
        for lowered, kw in self.calls.values():
            c = lowered.compile()
            mem = c.memory_analysis()
            lanes = lowered.in_avals[0][0].shape[0]
            log(f"[{backend}] pool program: lanes={lanes} "
                f"n_pad={kw['n']} cap={kw['cap']} block={kw['block']} "
                f"mode={kw['mode']}; memory_analysis temp="
                f"{mem.temp_size_in_bytes} argument="
                f"{mem.argument_size_in_bytes} output="
                f"{mem.output_size_in_bytes} alias={mem.alias_size_in_bytes}")
            out.append(c)
        return out


def _solve_all(cli, requests):
    """Submit every (name, knobs) at once; wait for all results.  Returns
    {label: (result dict, latency_s)}; any error response fails."""
    out, errors, threads = {}, [], []

    def wait(label, rid, t0):
        try:
            res = cli.result(rid)
            out[label] = (res, time.perf_counter() - t0)
        except Exception as e:          # noqa: BLE001 — report, then fail
            errors.append(f"{label}: {type(e).__name__}: {e}")

    for label, name, knobs in requests:
        t0 = time.perf_counter()
        rid = cli.submit(name, **knobs)
        th = threading.Thread(target=wait, args=(label, rid, t0))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    check(not errors, "requests ended in error: " + "; ".join(errors))
    return out


def run_pool(backend: str, golden: dict, clog: CompileLog, names=TABLE1,
             bloom=BLOOM) -> dict:
    """One ``TwServer`` pool through its client: a cold pass (compiles)
    and a warm pass of the same Table-1 requests, plus one bloom request.
    Returns the cold pass's results by instance name."""
    from repro.launch.twserved import TwServer
    from repro.serve.client import TwClient

    srv = TwServer(port=0, lanes=8, block=2048, backend=backend,
                   mode="sort", cache=0)
    srv.start()
    try:
        cli = TwClient("127.0.0.1", srv.port)
        table = [(name, name, {}) for name in names]
        mark = clog.mark()
        t0 = time.perf_counter()
        cold = _solve_all(cli, table + [("bloom:" + bloom, bloom,
                                         {"mode": "bloom"})])
        cold_s = time.perf_counter() - t0
        log(f"[{backend}] cold pass compiles: {clog.summary(mark)}")
        mark = clog.mark()
        t0 = time.perf_counter()
        warm = _solve_all(cli, table)
        warm_s = time.perf_counter() - t0
        log(f"[{backend}] warm pass compiles: {clog.summary(mark)}")
        pool = cli.metrics()["pool"]
    finally:
        srv.close()

    for label, (res, lat) in cold.items():
        log(f"[{backend}] {label}: width={res['width']} exact={res['exact']} "
            f"lb={res['lb']} ub={res['ub']} expanded={res['expanded']} "
            f"latency_cold_s={lat:.3f} latency_warm_s="
            + (f"{warm[label][1]:.3f}" if label in warm else "-"))
    log(f"[{backend}] pool wall: cold pass (includes compile) {cold_s:.3f}s, "
        f"warm pass {warm_s:.3f}s; dispatches="
        f"{pool['counters'].get('dispatches', 0)}")

    errors = pool["counters"].get("driver_errors", 0)
    check(errors == 0, f"[{backend}] driver thread caught {errors} "
                       "exception(s) from scheduler steps")
    for name in names:
        res = cold[name][0]
        if name == HEAVY:
            log(f"[{backend}] {name} exact={res['exact']}")
            check(res["lb"] <= golden[name] <= res["ub"],
                  f"[{backend}] {name}: lb {res['lb']} / ub {res['ub']} "
                  f"do not bracket tw {golden[name]}")
            if res["exact"]:
                check(res["width"] == golden[name],
                      f"[{backend}] {name}: width {res['width']} != "
                      f"{golden[name]}")
        else:
            check(res["exact"], f"[{backend}] {name} came back inexact")
            check(res["width"] == golden[name],
                  f"[{backend}] {name}: width {res['width']} != "
                  f"{golden[name]}")
        check(warm[name][0] == res,
              f"[{backend}] {name}: warm pass differs from cold pass")
    if "queen5_5" in names:
        got = cold["queen5_5"][0]["expanded"]
        check(got == QUEEN5_5_EXPANDED,
              f"[{backend}] queen5_5 expanded {got} != {QUEEN5_5_EXPANDED}")
    res = cold["bloom:" + bloom][0]
    check(res["width"] == golden[bloom],
          f"[{backend}] bloom {bloom}: width {res['width']} != "
          f"{golden[bloom]}")
    return {label: res for label, (res, _lat) in cold.items()}


def one_chip(golden: dict, cache_dir: str) -> None:
    import jax

    clog = CompileLog()
    results = {}
    for backend in ("jax", "pallas"):
        before = cache_entries(cache_dir)
        with _ProgramRecorder() as rec:
            results[backend] = run_pool(backend, golden, clog)
        mark = clog.mark()
        programs = rec.compiled(backend)
        log(f"[{backend}] compiling the pool programs again: "
            f"{clog.summary(mark)}; compile cache entries {before} -> "
            f"{cache_entries(cache_dir)}")
        stats = jax.devices()[0].memory_stats() or {}
        log(f"[{backend}] peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    jax_res, pallas_res = results["jax"], results["pallas"]
    check(all("tpu_custom_call" in c.as_text() for c in programs),
          "a pallas pool program holds no tpu_custom_call: the kernels "
          "ran in interpret mode")
    log(f"[pallas] all {len(programs)} pool programs contain "
        "tpu_custom_call: True")
    for label, want in jax_res.items():
        got = pallas_res[label]
        keys = ("width", "exact", "expanded", "per_k")
        same = all(got[k] == want[k] for k in keys)
        log(f"[parity] {label}: pallas == jax on {keys}: {same}")
        check(same, f"[parity] {label}: pallas {[got[k] for k in keys]} "
                    f"!= jax {[want[k] for k in keys]}")


# -------------------------------------------------------------- four chips

def four_chips(golden: dict) -> None:
    import jax
    from repro.core import bitset, distributed, graph, ladder

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = distributed.make_solver_mesh()
    cap_local = DISTRIBUTED_CAP // len(devs)

    def in_use():
        return [d.memory_stats()["bytes_in_use"] for d in devs]

    def peaks():
        return [d.memory_stats()["peak_bytes_in_use"] for d in devs]

    before = in_use()
    states, _counts = distributed.init_frontier(
        mesh, cap_local, bitset.n_words(36))
    held = in_use()
    log(f"[mesh] frontier states {states.shape} on "
        f"{len(states.sharding.device_set)} devices; bytes_in_use per device "
        f"{before} -> {held}")
    check(len(states.sharding.device_set) == 4,
          "the frontier is not sharded over all 4 devices")
    check(all(h > b for h, b in zip(held, before)),
          "bytes_in_use did not grow on every device")
    del states, _counts

    for name in DISTRIBUTED:
        g = graph.REGISTRY[name]()
        peak0 = peaks()
        t0 = time.perf_counter()
        dist = ladder.solve_distributed(g, mesh, cap_local=cap_local,
                                        block=1 << 10)
        dist_s = time.perf_counter() - t0
        grew = [p - q for p, q in zip(peaks(), peak0)]
        t0 = time.perf_counter()
        one = ladder.solve(g, block=1 << 10)
        one_s = time.perf_counter() - t0
        log(f"[mesh] {name}: 4 chips width={dist.width} exact={dist.exact} "
            f"expanded={dist.expanded} ({dist_s:.3f}s); 1 chip "
            f"width={one.width} exact={one.exact} expanded={one.expanded} "
            f"({one_s:.3f}s); peak_bytes_in_use growth per device {grew}")
        check((dist.width, dist.exact, dist.expanded)
              == (one.width, one.exact, one.expanded),
              f"[mesh] {name}: 4-chip and 1-chip results differ")
        check(dist.exact and dist.width == golden[name],
              f"[mesh] {name}: width {dist.width} != {golden[name]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed solver on 4 chips "
                         "against a one-chip solve")
    args = ap.parse_args(argv)

    import jax
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev['platform']}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.backend import enable_compile_cache
    cache_dir = enable_compile_cache()
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); "
        f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries); "
        f"jax {jax.__version__}")

    golden = golden_widths()
    try:
        if args.four_chips:
            four_chips(golden)
        else:
            one_chip(golden, cache_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
