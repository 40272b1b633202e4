"""The program's own spans over a window, for the per-layer
metrics that read them (``bench/metrics/model.*_ms.py``,
``bench/metrics/backend.*_ms.py``).

The program folds every real-clock span of a request (one ``model.forward``
and everything it causes, worker threads included) into the process's
``repro.telemetry.request_log``.  After the window the harness calls
nothing of the program (``check()`` runs only the reference), so the last
``len(rec.requests)`` records are exactly the window's forwards.  A program
without that log, and a log holding fewer records than the window (the
control system, which runs no program), give None.
"""
from __future__ import annotations


def window(rec):
    """The window's request records, oldest first, or None."""
    try:
        from repro.telemetry import request_log
    except ImportError:
        return None
    n = len(rec.requests)
    records = request_log.last(n)
    if n == 0 or len(records) < n:
        return None
    return records


def per_request_ms(rec, *names: str):
    """Mean milliseconds per request in the spans ``names``."""
    records = window(rec)
    if records is None:
        return None
    return sum(r.ms(name) for r in records for name in names) / len(records)


def per_span_ms(rec, name: str):
    """Mean milliseconds of one ``name`` span over the window."""
    records = window(rec)
    if records is None:
        return None
    spans = sum(r.n(name) for r in records)
    if spans == 0:
        return None
    return sum(r.ms(name) for r in records) / spans
