"""refills_per_level: mid-level refills of the lane buffers per level the
live lanes ran, in the window (pool counters ``refills`` /
``lane_levels``, deltas over the window).  None where the program has no
such counter."""


def read(rec):
    c0, c1 = rec["pool0"]["counters"], rec["pool1"]["counters"]
    if "refills" not in c1:
        return None
    levels = c1.get("lane_levels", 0) - c0.get("lane_levels", 0)
    if levels <= 0:
        return None
    return (c1["refills"] - c0.get("refills", 0)) / levels
