"""solves_per_s: exact answers read by the clients inside the window,
over the window's length (host clock).  Inexact, refused and failed
requests are not counted."""


def read(rec):
    n = sum(1 for r in rec["records"]
            if "result" in r and r["result"]["exact"]
            and r["done"] <= rec["window_s"])
    return n / rec["window_s"]
