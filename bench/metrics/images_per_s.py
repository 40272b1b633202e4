"""Images completed in the window over the window's length: first submit
to last answer (host clock)."""
from bench.stats import rate


def read(rec):
    return rate(rec.images, rec.window_s)
