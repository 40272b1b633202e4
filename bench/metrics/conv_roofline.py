"""Share of the roofline reached by the convs: the least time the chip
could take for the window's convs (their FLOPs at the bf16 peak, or their
bytes at HBM bandwidth, whichever is longer), over the device time of the
conv programs in the trace.  FLOPs and bytes come from the shapes of every
conv one forward runs (``bench/counts.py``), times the window's forwards."""
from bench.roofline import share


def read(rec):
    return share(rec, "conv")
