"""Master time per request in the program's ``model.norm`` spans: every
LayerNorm of a ConvNeXt forward (the stem's, each downsample's, each
block's and the head's).  Read from the process's request log: the last
``len(rec.requests)`` records, which are exactly the window's forwards
because the harness calls nothing of the program after the window; None
when the log holds fewer (``bench/spans.py``)."""
from bench.spans import per_request_ms


def read(rec):
    return per_request_ms(rec, "model.norm")
