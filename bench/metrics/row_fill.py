"""row_fill (%): frontier rows the live lanes expanded over the rows
their full-cap buffers held (levels x cap), in the window (pool counters
``lane_expanded`` / ``lane_row_slots``, deltas over the window).  None
where the program has no such counter."""


def read(rec):
    c0, c1 = rec["pool0"]["counters"], rec["pool1"]["counters"]
    rows = c1.get("lane_row_slots", 0) - c0.get("lane_row_slots", 0)
    if rows <= 0:
        return None
    live = c1.get("lane_expanded", 0) - c0.get("lane_expanded", 0)
    return 100.0 * live / rows
