"""Master time per request blocked on the coded runs' event queues: the
program's ``backend.wait`` spans, from dispatch until each run's accepting
arrival.  Read from the process's request log: the last
``len(rec.requests)`` records, which are exactly the window's forwards
because the harness calls nothing of the program after the window; None
when the log holds fewer (``bench/spans.py``)."""
from bench.spans import per_request_ms


def read(rec):
    return per_request_ms(rec, "backend.wait")
