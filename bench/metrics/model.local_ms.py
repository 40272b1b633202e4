"""Master time per request in master-local layers: the program's
``model.local`` spans (local convs, entry pads, post-decode activations and
pools, ResNet's stem, downsamples and skip adds, the head) plus
``model.remainder`` (each segment's remainder columns).  Read from the
process's request log: the last ``len(rec.requests)`` records, which are
exactly the window's forwards because the harness calls nothing of the
program after the window; None when the log holds fewer
(``bench/spans.py``)."""
from bench.spans import per_request_ms


def read(rec):
    return per_request_ms(rec, "model.local", "model.remainder")
