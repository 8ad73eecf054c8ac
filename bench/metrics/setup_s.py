"""setup_s: seconds from process start to the window's first request —
interpreter and JAX start, compile-cache loads, server start, warm-up and
any set-up solves (host clock)."""


def read(rec):
    return rec["setup_s"]
