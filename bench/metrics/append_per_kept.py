"""append_per_kept: rows the live lanes' levels appended to their buffers
(after each chunk's own dedup) per frontier row they expanded, in the
window (pool counters ``appended_rows`` / ``lane_expanded``, deltas over
the window): the rows a level writes and sorts for each distinct row it
keeps.  None where the program has no such counter."""


def read(rec):
    c0, c1 = rec["pool0"]["counters"], rec["pool1"]["counters"]
    if "appended_rows" not in c1:
        return None
    kept = c1.get("lane_expanded", 0) - c0.get("lane_expanded", 0)
    if kept <= 0:
        return None
    return (c1["appended_rows"] - c0.get("appended_rows", 0)) / kept
