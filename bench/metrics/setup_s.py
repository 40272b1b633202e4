"""Set-up seconds: process start to the first request of the window —
start-up, weights and inputs, and the warm-up compiles (host clock)."""


def read(rec):
    return rec.setup_s
