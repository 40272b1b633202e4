"""Mean measured compute of a coded piece that started: the program's
``backend.compute`` spans (the piece's chain run to ``block_until_ready``
on its worker), without the injected straggler delay.  Read from the
process's request log: the last ``len(rec.requests)`` records, which are
exactly the window's forwards because the harness calls nothing of the
program after the window; None when the log holds fewer
(``bench/spans.py``)."""
from bench.spans import per_span_ms


def read(rec):
    return per_span_ms(rec, "backend.compute")
