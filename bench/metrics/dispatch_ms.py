"""dispatch_ms: the lane engine's launch-to-result wall time per host
sync in the window (pool telemetry ``dispatch_wall_s`` / ``host_syncs``,
deltas over the window)."""


def read(rec):
    t0 = rec["pool0"]["timings"].get("dispatch_wall_s", {})
    t1 = rec["pool1"]["timings"].get("dispatch_wall_s", {})
    syncs = (rec["pool1"]["counters"].get("host_syncs", 0)
             - rec["pool0"]["counters"].get("host_syncs", 0))
    if syncs <= 0:
        return None
    return 1000.0 * (t1.get("total_s", 0.0) - t0.get("total_s", 0.0)) / syncs
