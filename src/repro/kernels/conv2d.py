"""Pallas TPU kernel: direct 2D convolution (the paper's type-1 subtask).

TPU adaptation (DESIGN.md §3): the CoCoI width split already bounds each
worker's input partition, so the kernel holds the whole partition
(C_I, H_I, W_I^p) in VMEM and tiles the OUTPUT CHANNELS across the grid —
the K*K accumulation becomes K^2 MXU-friendly (C_I x C_O-block) contractions
instead of an im2col materialisation:

  grid  = (C_O // BLOCK_CO,)
  x     : (C_I, H_I, W_I)            VMEM-resident partition
  w     : (BLOCK_CO, C_I, K, K)      this step's out-channel tile
  out   : (BLOCK_CO, H_O, W_O)

Accumulation runs in f32 regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["conv2d_pallas", "BLOCK_CO"]

BLOCK_CO = 32


def _conv_kernel(x_ref, w_ref, o_ref, *, kernel: int, stride: int,
                 h_out: int, w_out: int):
    x = x_ref[...]  # (C_I, H_I, W_I)
    w = w_ref[...]  # (BLOCK_CO, C_I, K, K)
    acc = jnp.zeros(o_ref.shape, jnp.float32)  # (BLOCK_CO, H_O, W_O)
    for kh in range(kernel):
        for kw in range(kernel):
            patch = jax.lax.slice(
                x,
                (0, kh, kw),
                (x.shape[0], kh + (h_out - 1) * stride + 1,
                 kw + (w_out - 1) * stride + 1),
                (1, stride, stride),
            )  # (C_I, H_O, W_O)
            acc += jnp.einsum(
                "chw,oc->ohw", patch.astype(jnp.float32),
                w[:, :, kh, kw].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "block_co", "interpret"))
def conv2d_pallas(x: jax.Array, w: jax.Array, stride: int = 1, *,
                  block_co: int = BLOCK_CO, interpret: bool) -> jax.Array:
    """x: (C_I, H_I, W_I), w: (C_O, C_I, K, K) -> (C_O, H_O, W_O).

    The caller chooses ``interpret``; the TPU compiler does not yet accept
    this kernel (the patch reshape is an unsupported shape cast), so only
    interpret mode runs it today.
    """
    c_in, h_in, w_in = x.shape
    c_out, c_in2, K, K2 = w.shape
    assert c_in == c_in2 and K == K2
    h_out = (h_in - K) // stride + 1
    w_out = (w_in - K) // stride + 1
    block_co = min(block_co, c_out)
    pad = -c_out % block_co
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0), (0, 0), (0, 0)))
    cop = c_out + pad
    kern = functools.partial(_conv_kernel, kernel=K, stride=stride,
                             h_out=h_out, w_out=w_out)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((cop, h_out, w_out), x.dtype),
        grid=(cop // block_co,),
        in_specs=[
            pl.BlockSpec((c_in, h_in, w_in), lambda i: (0, 0, 0)),
            pl.BlockSpec((block_co, c_in, K, K), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_co, h_out, w_out), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(x, w)
    return out[:c_out]
