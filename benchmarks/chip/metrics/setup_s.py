"""Set-up: process start to the window's opening (weights, warm-up,
compilation or loading from the persistent cache)."""


def read(rec):
    return rec["setup_s"]
