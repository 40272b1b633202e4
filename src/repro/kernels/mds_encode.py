"""Pallas TPU kernel: MDS encode GEMM  G (n, k) @ X (k, F) -> (n, F).

The paper's encode (eq. 3) is a skinny GEMM over the flattened input
partitions: k is tiny (<= 16), F is huge (B*C_I*H_I*W_I^p).  On the Pi
this runs on the master CPU; on TPU it is purely memory-bound, so the
kernel streams F through VMEM in lane-aligned tiles.

The decode GEMM (kernels/mds_decode.py) has the identical structure with
D = G_S^{-1}, and both backends' per-piece GEMM (a coded token slice times
an FFN weight, dist/executor.py and dist/mesh_exec.py) is the same kernel
at transformer widths, so all three delegate to one tiled
``skinny_gemm_pallas``:

  grid  = (M / bm, N / bn, K / bk)        K innermost, f32 accumulator
  A     : (bm, bk)   x : (bk, bn)   out : (bm, bn)

Rows and the contraction each form one full-extent block when they fit
their cap (the encode's (n, k) generator stays resident); a larger one is
cut into the largest aligned block that divides it, or padded.  Output
columns always stream in ``block_f``-wide blocks (padded up), so every
grid step computes the same (bm, bk) @ (bk, block_f) product whether a
decode runs whole or split column-wise across a mesh — which keeps the two
backends byte-identical.  The caps keep the double-buffered blocks near
8 MiB of f32, inside the v5e's default scoped VMEM at every gemma-2b /
minicpm-2b FFN shape.

``interpret=None`` picks interpret mode on the CPU backend only; on any
other backend the kernel is compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["skinny_gemm_pallas", "mds_encode_pallas", "interpret_default",
           "BLOCK_F"]

BLOCK_F = 512      # streamed output-column block (lanes)
BLOCK_M = 256      # cap of the row block (sublanes)
BLOCK_K = 1024     # cap of the contraction block


def interpret_default(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret=None`` request: interpret on the CPU only, so
    a run on an accelerator never silently falls back to the interpreter."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def _block(dim: int, cap: int, align: int) -> tuple[int, int]:
    """(block, padded extent) for one dimension: the whole extent when it
    fits ``cap``, else the largest ``align``-multiple <= cap that divides
    it (no copy), else ``cap`` with the extent padded up to a multiple."""
    if dim <= cap:
        return dim, dim
    for b in range(cap - cap % align, cap // 4 - 1, -align):
        if dim % b == 0:
            return b, dim
    return cap, dim + (-dim % cap)


def _gemm_blocks(m: int, b: int, f: int, *, block_f: int = BLOCK_F
                ) -> tuple[tuple[int, int], tuple[int, int], tuple[int, int]]:
    """Block plan of an (m, b) @ (b, f) product: ((bm, Mp), (bk, Kp),
    (bn, Fp)) — block size and padded extent of each dimension."""
    return (_block(m, BLOCK_M, 16), _block(b, BLOCK_K, 128),
            (block_f, f + (-f % block_f)))


def _gemm_kernel(a_ref, x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: f32 operands multiply in full f32 on the MXU — a coded
    # piece is a linear mix, and the decode amplifies any rounding in it
    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def skinny_gemm_pallas(A: jax.Array, x: jax.Array, *, block_f: int = BLOCK_F,
                       interpret: bool | None = None) -> jax.Array:
    """A: (m, b), x: (b, F) -> (m, F) in x's dtype, accumulated in f32.

    Padding (only where no aligned block divides a dimension) is added
    internally and sliced off.
    """
    interpret = interpret_default(interpret)
    m, b = A.shape
    bx, F = x.shape
    assert bx == b, (A.shape, x.shape)
    (bm, Mp), (bk, Kp), (bn, Fp) = _gemm_blocks(m, b, F, block_f=block_f)
    A = A.astype(x.dtype)
    if (Mp, Kp) != (m, b):
        A = jnp.pad(A, ((0, Mp - m), (0, Kp - b)))
    if (Kp, Fp) != (b, F):
        x = jnp.pad(x, ((0, Kp - b), (0, Fp - F)))
    out = pl.pallas_call(
        _gemm_kernel,
        out_shape=jax.ShapeDtypeStruct((Mp, Fp), x.dtype),
        grid=(Mp // bm, Fp // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="skinny_gemm",
    )(A, x)
    return out[:m, :F]


def mds_encode_pallas(G: jax.Array, x: jax.Array, *, block_f: int = BLOCK_F,
                      interpret: bool | None = None) -> jax.Array:
    """G: (n, k), x: (k, F) -> (n, F): the paper's encode GEMM (eq. 3)."""
    return skinny_gemm_pallas(G, x, block_f=block_f, interpret=interpret)
