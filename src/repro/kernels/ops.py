"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` runs a kernel in interpret mode on the CPU backend only
(tests and CPU rehearsals); on any other backend the kernel is compiled,
so a run on the chip never silently falls back to the interpreter.  Each
wrapper has a pure-jnp oracle in ref.py; tests/test_kernels.py sweeps
shapes/dtypes and asserts allclose.
"""
from __future__ import annotations

import jax

from .conv2d import conv2d_pallas
from .mds_decode import mds_decode_pallas
from .mds_encode import interpret_default, mds_encode_pallas
from .ssd_scan import ssd_chunk_pallas

__all__ = ["mds_encode", "mds_decode", "conv2d_subtask", "ssd_chunk",
           "interpret_default"]


def mds_encode(G: jax.Array, x: jax.Array, *, interpret: bool | None = None
               ) -> jax.Array:
    """Encode k flattened partitions into n coded rows (paper eq. 3)."""
    return mds_encode_pallas(G, x, interpret=interpret)


def mds_decode(D: jax.Array, y: jax.Array, *, interpret: bool | None = None
               ) -> jax.Array:
    """Recover k source rows from received coded rows: D @ Y (paper eq. 4)."""
    return mds_decode_pallas(D, y, interpret=interpret)


def conv2d_subtask(x: jax.Array, w: jax.Array, stride: int = 1, *,
                   interpret: bool | None = None) -> jax.Array:
    """One worker's conv subtask (C_I, H, W^p) -> (C_O, H_O, W_O^p)."""
    return conv2d_pallas(x, w, stride, interpret=interpret_default(interpret))


def ssd_chunk(x, dt, A, Bm, Cm, h0, *, interpret: bool | None = None):
    """One Mamba2 SSD chunk (see kernels/ssd_scan.py)."""
    return ssd_chunk_pallas(x, dt, A, Bm, Cm, h0,
                            interpret=interpret_default(interpret))
