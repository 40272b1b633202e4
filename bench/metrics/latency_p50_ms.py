"""Median request latency over every request of the window, submit to
logits ready (host clock)."""
from bench.stats import percentile


def read(rec):
    p = percentile([r.latency_s for r in rec.requests], 50)
    return None if p is None else p * 1e3
