"""Pallas TPU kernel: MDS decode GEMM  D (k, m) @ Y (m, F) -> (k, F).

The any-k decode (paper eq. 4) is the mirror image of the encode: a tiny
decode matrix D = G_S^{-1} (k <= 16, cached host-side — see
core/coding.py:decode_matrix_cached) against the huge flattened worker
outputs Y.  Structurally it is the same resident-matrix streaming GEMM as
the encode, so it delegates to ``skinny_gemm_pallas``
(kernels/mds_encode.py) — one kernel body, two named entry points.

``m`` is the number of received coded rows (m == k for MDS fastest-k; the
LT scheme may decode from m > k rows via its host-side least-squares,
which does not use this kernel).  ``interpret=None`` means interpret mode
on the CPU backend only, as for the encode.
"""
from __future__ import annotations

import jax

from .mds_encode import BLOCK_F, skinny_gemm_pallas

__all__ = ["mds_decode_pallas", "BLOCK_F"]


def mds_decode_pallas(D: jax.Array, y: jax.Array, *, block_f: int = BLOCK_F,
                      interpret: bool | None = None) -> jax.Array:
    """D: (k, m), y: (m, F) -> (k, F): the any-k decode GEMM (eq. 4)."""
    return skinny_gemm_pallas(D, y, block_f=block_f, interpret=interpret)
