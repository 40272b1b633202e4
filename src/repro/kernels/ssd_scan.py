"""Pallas TPU kernel: one SSD chunk (Mamba2 intra-chunk dual form).

Processes a (chunk L, heads H, head_dim P, state N) tile per grid step:
the quadratic intra-chunk term plus the incoming-state contribution and
the chunk's outgoing state, exactly the math of
``repro.models.ssm.ssd_chunked`` for a single chunk:

  grid  = (B,)   (one batch element per step; callers vmap/scan chunks)
  x     : (L, H, P)   dt: (L, H)   B,C: (L, N)   h0: (H, P, N)
  y     : (L, H, P)   h1: (H, P, N)

All math in f32 in VMEM.  L is the paper-facing perf lever (VMEM footprint
~ L*(H*P + 2N) + H*L^2); 128 keeps every operand MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ssd_chunk_pallas"]


def _segsum(dA):
    L = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    M = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, M, -jnp.inf)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h1_ref):
    x = x_ref[0].astype(jnp.float32)      # (L, H, P)
    dt = dt_ref[0].astype(jnp.float32)    # (L, H)
    A = a_ref[...].astype(jnp.float32)    # (H,)
    Bm = b_ref[0].astype(jnp.float32)     # (L, N)
    Cm = c_ref[0].astype(jnp.float32)     # (L, N)
    h0 = h0_ref[0].astype(jnp.float32)    # (H, P, N)

    dA = dt * A[None, :]                  # (L, H)
    # intra-chunk quadratic term
    Lmat = jnp.exp(_segsum(dA.T))         # (H, L, L) decay l<-s
    CB = Cm @ Bm.T                        # (L, L)
    y_intra = jnp.einsum("hls,ls,sh,shp->lhp", Lmat, CB, dt, x)
    # incoming state contribution
    cum = jnp.cumsum(dA, axis=0)          # (L, H)
    y_inter = jnp.einsum("ln,lh,hpn->lhp", Cm, jnp.exp(cum), h0)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # outgoing state
    decay_to_end = jnp.exp(cum[-1:] - cum)  # (L, H)
    S = jnp.einsum("ln,lh,lh,lhp->hpn", Bm, decay_to_end, dt, x)
    h1_ref[0] = h0 * jnp.exp(cum[-1])[:, None, None] + S


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(x, dt, A, Bm, Cm, h0, *, interpret: bool):
    """Batched one-chunk SSD.

    x: (B, L, H, P), dt: (B, L, H), A: (H,), Bm/Cm: (B, L, N),
    h0: (B, H, P, N) -> (y: (B, L, H, P), h1: (B, H, P, N)).
    """
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    y, h1 = pl.pallas_call(
        _ssd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, L, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, L, H, P), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, L, H), lambda b: (b, 0, 0)),
            pl.BlockSpec((H,), lambda b: (0,)),
            pl.BlockSpec((1, L, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, L, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b: (b, 0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, L, H, P), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b: (b, 0, 0, 0)),
        ),
        interpret=interpret,
    )(x, dt, A, Bm, Cm, h0)
    return y, h1
