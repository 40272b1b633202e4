"""Master time per request in the program's ``model.encode`` spans: the
entry partition slices, their stack, the encode GEMM and the per-piece
selections of every coded segment.  Read from the process's request log:
the last ``len(rec.requests)`` records, which are exactly the window's
forwards because the harness calls nothing of the program after the
window; None when the log holds fewer (``bench/spans.py``)."""
from bench.spans import per_request_ms


def read(rec):
    return per_request_ms(rec, "model.encode")
