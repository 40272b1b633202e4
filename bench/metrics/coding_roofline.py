"""Share of the roofline reached by the coding GEMMs (MDS encode and
decode): their FLOPs at the bf16 peak or their bytes at HBM bandwidth,
whichever is longer, over the device time of the coding programs in the
trace.  Counted from the GEMM shapes of one forward (``bench/counts.py``),
times the window's forwards."""
from bench.roofline import share


def read(rec):
    return share(rec, "coding")
