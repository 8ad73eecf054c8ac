"""lane_fill (%): live lanes over the lane slots the multi-lane
dispatches padded them to, in the window (pool counters
``lanes_decided`` / ``lane_slots``, deltas over the window).  None where
the program has no such counter."""


def read(rec):
    c0, c1 = rec["pool0"]["counters"], rec["pool1"]["counters"]
    slots = c1.get("lane_slots", 0) - c0.get("lane_slots", 0)
    if slots <= 0:
        return None
    live = c1.get("lanes_decided", 0) - c0.get("lanes_decided", 0)
    return 100.0 * live / slots
