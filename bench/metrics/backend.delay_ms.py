"""Worker time per request in injected delay: the program's
``backend.delay`` spans (the straggler's or delay model's sleep after a
piece's measured compute, to its arrival or its cancellation), summed
over the request's pieces: what the straggler costs the pool, which the
k-of-n early exit keeps off the master's wait.  Read from the process's
request log: the last ``len(rec.requests)`` records, which are exactly
the window's forwards because the harness calls nothing of the program
after the window; None when the log holds fewer (``bench/spans.py``)."""
from bench.spans import per_request_ms


def read(rec):
    return per_request_ms(rec, "backend.delay")
