"""admit_ms: host time of one request's admission work (preprocess,
bounds, first block plan) in the window: the pool's ``tw.admit`` span,
total over calls (pool telemetry, deltas over the window).  None where
the program has no such span."""


def read(rec):
    t0 = rec["pool0"]["timings"].get("tw.admit", {})
    t1 = rec["pool1"]["timings"].get("tw.admit", {})
    calls = t1.get("calls", 0) - t0.get("calls", 0)
    if calls <= 0:
        return None
    return 1000.0 * (t1["total_s"] - t0.get("total_s", 0.0)) / calls
