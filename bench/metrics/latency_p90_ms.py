"""90th percentile of the latency of every request of the window (host
clock)."""
from bench.stats import percentile


def read(rec):
    p = percentile([r.latency_s for r in rec.requests], 90)
    return None if p is None else p * 1e3
