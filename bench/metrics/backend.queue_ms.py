"""Mean time a coded piece that started waited in its worker's inbox: the
program's ``backend.queue`` spans, dispatch to the worker's start.  Read
from the process's request log: the last ``len(rec.requests)`` records,
which are exactly the window's forwards because the harness calls nothing
of the program after the window; None when the log holds fewer
(``bench/spans.py``)."""
from bench.spans import per_span_ms


def read(rec):
    return per_span_ms(rec, "backend.queue")
