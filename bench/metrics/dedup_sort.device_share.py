"""dedup_sort.device_share (%): device time of the XLA sort ops (the
exact dedup of core/dedup.py and engine.expand_chunk) over the device's
busy time, from the profiler trace of the window."""


def read(rec):
    t = rec["trace"]
    if not t or not t["busy_s"] or not t["sort_s"]:
        return None
    return 100.0 * t["sort_s"] / t["busy_s"]
