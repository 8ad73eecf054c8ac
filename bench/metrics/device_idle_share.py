"""device_idle_share (%): 1 - (union of the device's op intervals) /
(traced window), from the profiler trace of the window."""


def read(rec):
    t = rec["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
