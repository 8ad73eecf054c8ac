"""Run one benchmark cell of the treewidth solve service on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the pool's settings and its instance
family, ``bench/instances/<family>.py``) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/arrivals.py``).  The run
starts an embedded ``TwServer`` (``repro.launch.twserved``) in this
process, which is the one that holds the chip, and sends every request
over TCP through ``TwClient`` as an explicit ``n`` + ``edges`` graph.

Set-up (``setup_s``, from process start): the persistent compile cache
(``enable_compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``, else inside
the checkout), the server, and one warm-up request of each instance of
the mix (drawn from a seed stream of their own and cancelled after their
first rung, so only the pool's program shapes are compiled).  Then the
window: ``--seconds`` of closed- or open-loop traffic.  After it, every
answer due in the window is awaited (at most a minute past the close),
the server is shut down, and each answer is checked against the graph's
published treewidth, or against the plain reference
(``bench/reference.py``, in worker processes) where the instance family
gives none.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the profiler on for the window and
the trace reduced by ``bench/trace_reduce.py``.  Each metric is read by
``bench/metrics/<name>.py`` from the run's record.  The last stdout line
is one JSON object; the numbers compared to decide ``correct`` are
printed beside their limits as the last lines of stderr and as the last
key of that object.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import importlib.util
import json
import multiprocessing
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# how long after the window's close an answer due in it is awaited
LATE_S = 60.0
# answers checked against the reference in one run, at most (a sample
# drawn from the seed beyond that)
CHECK_MAX = 400
# the numbers that decide ``correct``, each with its limit (PERF.md
# section 2 gives the readings they were set from)
LIMITS = {"wrong_width": 0, "not_exact": 0, "unanswered": 0}


class NoChip(Exception):
    pass


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell spec

def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, trace: bool) -> dict:
    """The cell's entry, configuration, traffic mix and the metrics this
    run reports, from ``BENCHMARK.json`` and the files it names."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind]
               if name in m.get("workloads", [name])]
    return {"cell": cell,
            "config": load_json(BENCH, "configs", cell["config"] + ".json"),
            "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            "metrics": metrics}


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- the device

def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def memory_peak_bytes(count: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:count])


def enable_cache() -> None:
    """The program's cache helper: ``$JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``.jax_cache/`` at the checkout root, with the checkout
    path stripped from the keys of Pallas programs.  Every program is
    written, however fast it compiled."""
    import jax
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Compiles of this process, from ``jax.monitoring`` events: (program
    name, seconds, served from the persistent cache)."""

    def __init__(self):
        import jax.monitoring as mon
        self.programs = []
        self._hit = False
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        self._hit = self._hit or event == CACHE_HIT

    def _on_duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE:
            self.programs.append((kw.get("fun_name", "?"), secs, self._hit))
            self._hit = False

    def since(self, mark: int) -> list:
        return self.programs[mark:]


# ------------------------------------------------------------ the service

class Service:
    """The embedded server and its client."""

    def __init__(self, config: dict, knobs: dict):
        from repro.launch.twserved import TwServer
        from repro.serve.client import TwClient
        self.srv = TwServer(port=0, cap_max=int(config["frontier_cap"]),
                            **config["pool"])
        self.srv.start()
        self.cli = TwClient("127.0.0.1", self.srv.port)
        self.knobs = dict(knobs)

    def submit(self, req: dict) -> int:
        """Send one request as an explicit graph (``n`` + ``edges``)."""
        from repro.core.graph import from_edges
        g = from_edges(int(req["n"]), req["edges"], name=req["key"])
        return self.cli.submit(g, **self.knobs)

    def idle(self) -> bool:
        s = self.srv.sched
        return not (s.in_flight or s.pool.busy)

    def wait_idle(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        while not self.idle():
            if time.monotonic() > end:
                raise RuntimeError("the pool did not go idle in set-up")
            time.sleep(0.02)

    def close(self) -> None:
        self.srv.close()


def warm(svc: Service, reqs: list) -> None:
    """Send the warm-up requests together; cancel each after its first
    rung is decided (or keep its answer if it finishes first)."""
    def one(req):
        rid = svc.submit(req)
        for ev in svc.cli.stream(rid):
            if ev.get("event") in ("rung_decided", "done", "error",
                                   "cancelled"):
                break
        svc.cli.cancel(rid)

    threads = [threading.Thread(target=one, args=(r,)) for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.wait_idle(600)


# ------------------------------------------------------------- the window

class Window:
    """Requests of the timed window: due, sent and answered times (host
    clock, seconds from the window's start) and the answers."""

    def __init__(self, svc: Service, seconds: float):
        self.svc = svc
        self.seconds = seconds
        self.records = []
        self.lock = threading.Lock()
        self.t0 = None
        self.threads = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _serve(self, rec: dict) -> None:
        """Send one request and wait for its answer."""
        import jax
        rec["sent"] = self.now()
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                rec["rid"] = self.svc.submit(rec["req"])
            left = self.seconds + LATE_S - self.now()
            with jax.profiler.TraceAnnotation("bench.await"):
                rec["result"] = self.svc.cli.result(
                    rec["rid"], read_timeout=max(0.001, left))
            rec["done"] = self.now()
        except Exception as e:      # noqa: BLE001 — the answer is missing
            rec["error"] = f"{type(e).__name__}: {e}"

    def closed(self, source, clients: int) -> None:
        """``clients`` callers, each sending its next request when its last
        one returns, until the window closes."""
        take = threading.Lock()

        def caller():
            while self.now() < self.seconds:
                with take:
                    rec = {"req": next(source), "due": self.now()}
                    with self.lock:
                        self.records.append(rec)
                self._serve(rec)

        self._start([threading.Thread(target=caller)
                     for _ in range(clients)])

    def open(self, arrivals: list) -> None:
        """Each request sent at its due time, whatever the service does;
        one waiting thread per request in flight."""
        def dispatcher():
            for due, req in arrivals:
                delay = due - self.now()
                if delay > 0:
                    time.sleep(delay)
                rec = {"req": req, "due": due}
                with self.lock:
                    self.records.append(rec)
                t = threading.Thread(target=self._serve, args=(rec,))
                t.start()
                with self.lock:
                    self.threads.append(t)

        self._start([threading.Thread(target=dispatcher)])

    def _start(self, threads: list) -> None:
        self.t0 = time.perf_counter()
        with self.lock:
            self.threads.extend(threads)
        for t in threads:
            t.start()

    def join(self) -> None:
        """Wait for every request of the window, at most ``LATE_S`` past
        the close; those still open then are unanswered."""
        end = time.perf_counter() + max(0.0, self.seconds + LATE_S
                                        - self.now()) + 5.0
        while True:
            with self.lock:
                alive = [t for t in self.threads if t.is_alive()]
            if not alive or time.perf_counter() > end:
                break
            alive[0].join(timeout=0.2)


# ------------------------------------------------------------ correctness

def check(records: list, seed: int, processes: int) -> dict:
    """Compare the answers with the reference.  Every answer is checked,
    or a sample of ``CHECK_MAX`` drawn from the seed.  A request whose
    family gives the graph's published treewidth (``width``) is checked
    against it; for the others the reference runs once per instance the
    benchmark knows to be the same graph up to relabelling
    (``ref_key``)."""
    from bench import arrivals, reference
    answered = [r for r in records if "result" in r]
    unanswered = len(records) - len(answered)
    not_exact = sum(1 for r in answered if not r["result"]["exact"])
    sample = answered
    if len(answered) > CHECK_MAX:
        rng = arrivals.stream(seed, arrivals.SAMPLE)
        keep = sorted(rng.choice(len(answered), CHECK_MAX, replace=False))
        sample = [answered[int(i)] for i in keep]
    ref, graphs = {}, {}
    for r in sample:
        if "width" in r["req"]:
            ref[r["req"]["ref_key"]] = r["req"]["width"]
        else:
            graphs.setdefault(r["req"]["ref_key"], r["req"])
    t0 = time.perf_counter()
    if graphs:
        with concurrent.futures.ProcessPoolExecutor(
                max_workers=max(1, min(processes, len(graphs))),
                mp_context=multiprocessing.get_context("spawn")) as ex:
            futs = {k: ex.submit(reference.treewidth, g["n"], g["edges"])
                    for k, g in graphs.items()}
            ref.update((k, f.result()) for k, f in futs.items())

    def wrong_answer(r):
        res, tw = r["result"], ref[r["req"]["ref_key"]]
        if res["exact"]:
            return res["width"] != tw
        return not res["lb"] <= tw <= res["ub"]

    wrong = [r for r in sample if wrong_answer(r)]
    for r in wrong[:5]:
        log(f"[check] {r['req']['key']} rid {r.get('rid')}: width "
            f"{r['result']['width']} exact {r['result']['exact']}, "
            f"reference {ref[r['req']['ref_key']]}")
    return {"numbers": {"wrong_width": len(wrong), "not_exact": not_exact,
                        "unanswered": unanswered},
            "checked": len(sample), "reference_graphs": len(graphs),
            "reference_s": time.perf_counter() - t0}


# ------------------------------------------------------------------ a run

def run_cell(loaded: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, knobs: dict = None,
             processes: int = 8) -> dict:
    """One run of a cell; returns the result object (the last stdout line)
    plus the numbers compared under ``checks``."""
    import jax
    from bench import arrivals
    config, traffic, cell = loaded["config"], loaded["traffic"], \
        loaded["cell"]
    dev = device_info(int(cell["chips"]), require_tpu)
    enable_cache()
    clog = CompileLog()
    svc = Service(config, knobs or {})
    try:
        warm(svc, arrivals.warmup(config, traffic, seed))
        if traffic["loop"] == "open":
            plan = arrivals.open_plan(config, traffic, seed, seconds)
        pool0 = svc.cli.metrics()["pool"]
        mark = len(clog.programs)
        setup_s = process_age_s()
        win = Window(svc, seconds)
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("bench.window"):
            if traffic["loop"] == "closed":
                win.closed(arrivals.closed_source(config, traffic, seed),
                           int(traffic["clients"]))
            else:
                win.open(plan)
            time.sleep(max(0.0, seconds - win.now()))
            window_s = win.now()
        if trace:
            jax.profiler.stop_trace()
        pool1 = svc.cli.metrics()["pool"]
        in_window = clog.since(mark)
        win.join()
        requests = svc.cli.metrics()["requests"]
        for rec in win.records:
            if "result" not in rec and "rid" in rec:
                svc.cli.cancel(rec["rid"])
        peak = memory_peak_bytes(dev["count"])
    finally:
        svc.close()
    records = win.records
    late = [r["sent"] - r["due"] for r in records if "sent" in r]
    log(f"[window] {len(records)} requests due, {window_s:.3f} s; "
        f"generator lateness p50 {statistics.median(late or [0]):.6f} s "
        f"max {max(late, default=0.0):.6f} s")
    log(f"[window] programs compiled or loaded inside the window (there "
        f"should be none): {len(in_window)} "
        + ", ".join(f"{n} {s:.3f}s{' (cache)' if h else ''}"
                    for n, s, h in in_window))
    result = check(records, seed, processes)
    log(f"[check] {result['checked']} answers checked against "
        f"{result['reference_graphs']} reference solves in "
        f"{result['reference_s']:.3f} s")
    rec = {"records": records, "seconds": seconds, "window_s": window_s,
           "late_s": LATE_S, "device": dev,
           "setup_s": setup_s, "pool0": pool0, "pool1": pool1,
           "requests": requests, "config": config, "traffic": traffic,
           "trace": None, "compiles_in_window": len(in_window)}
    device = dict(dev, memory_peak_bytes=peak)
    out = {}
    if trace:
        from bench import trace_reduce
        rec["trace"] = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"][:10],
                            "idle_gaps": rec["trace"]["idle_gaps"][:10]}
    metrics = {}
    for m in loaded["metrics"]:
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    numbers = result["numbers"]
    failed = sum(1 for r in records
                 if "result" not in r or not r["result"]["exact"])
    correct = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    return dict({"correct": correct, "attempted": len(records),
                 "failed": failed, "metrics": metrics, "device": device,
                 "compiles_in_window": len(in_window)},
                **out,
                checks={k: {"value": numbers[k], "limit": LIMITS[k]}
                        for k in LIMITS})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the control: every request with the submit "
                         "knobs of the configuration's \"control\" entry "
                         "(a frontier far below the stated one), which "
                         "breaks the exactness the configuration states; "
                         "correct must come out false")
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload, bool(args.trace))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    knobs = loaded["config"]["control"] if args.control else {}
    try:
        out = run_cell(loaded, args.seed, args.seconds, bool(args.trace),
                       knobs=knobs, processes=min(8, os.cpu_count() or 1))
    except NoChip as e:
        log(f"bench.run: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"[correct] {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
