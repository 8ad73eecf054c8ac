"""host_round_ms: host time of the driver per scheduler round in the
window, the device wait left out: (``tw.step`` - ``tw.wait``) total over
the ``tw.sync`` calls (pool telemetry, deltas over the window).  None
where the program has no such spans."""


def _delta(rec, name, key):
    t0 = rec["pool0"]["timings"].get(name, {})
    t1 = rec["pool1"]["timings"].get(name, {})
    return t1.get(key, 0) - t0.get(key, 0)


def read(rec):
    syncs = _delta(rec, "tw.sync", "calls")
    if syncs <= 0:
        return None
    host_s = _delta(rec, "tw.step", "total_s") \
        - _delta(rec, "tw.wait", "total_s")
    return 1000.0 * host_s / syncs
