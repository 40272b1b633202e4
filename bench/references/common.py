"""Plain float32 building blocks shared by the CNN references.

Written in ``jax.numpy`` and ``lax.conv`` only; nothing here imports the
system under test.  ``mode`` picks the arithmetic of every conv and matmul:

* ``"highest"`` — float32 products (``Precision.HIGHEST``), the precision
  the configurations state;
* ``"high"`` — XLA's ``Precision.HIGH``: three bfloat16 passes on the chip,
  the precision one step below ``highest``; the CPU computes it in float32;
* ``"bf16_3x"`` — the same three-pass product emulated explicitly (hi*hi +
  hi*lo + lo*hi of the bfloat16 halves of each float32 operand, multiplied
  exactly and accumulated in float32), so that it reads alike on the CPU and
  on the chip.

``high`` and ``bf16_3x`` serve as the correctness comparison's control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("highest", "high", "bf16_3x")


def he_normal(key, shape, fan_in: int) -> jax.Array:
    """He-normal float32 weights: N(0, 2 / fan_in)."""
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def _bf16(a: jax.Array) -> jax.Array:
    """a rounded to bfloat16's 8-bit mantissa, kept in float32 (a rounding
    the compiler may not drop, as it may a round trip through bfloat16)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _halves(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _three_pass(op, a: jax.Array, b: jax.Array) -> jax.Array:
    ah, al = _halves(a)
    bh, bl = _halves(b)
    return op(ah, bh) + (op(ah, bl) + op(al, bh))


def _apply(op, a, b, mode: str) -> jax.Array:
    if mode == "highest":
        return op(a, b, HIGHEST)
    if mode == "high":
        return op(a, b, jax.lax.Precision.HIGH)
    if mode == "bf16_3x":
        return _three_pass(functools.partial(op, precision=HIGHEST), a, b)
    raise ValueError(f"unknown arithmetic mode {mode!r}; known: {MODES}")


def conv(x: jax.Array, w: jax.Array, stride: int, pad: int,
         mode: str = "highest") -> jax.Array:
    """Zero-padded 2-D convolution, NCHW input, OIHW weight, no bias."""
    def op(a, b, precision):
        return jax.lax.conv_general_dilated(
            a, b, window_strides=(stride, stride),
            padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=precision)
    return _apply(op, x, w, mode)


def dense(x: jax.Array, w: jax.Array, mode: str = "highest") -> jax.Array:
    """x (B, F) @ w (F, C), no bias."""
    def op(a, b, precision):
        return jnp.dot(a, b, precision=precision)
    return _apply(op, x, w, mode)


def maxpool(x: jax.Array, window: int, stride: int) -> jax.Array:
    """VALID max pool over H and W (NCHW)."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, window, window),
                                 (1, 1, stride, stride), "VALID")


def head(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """Flatten C, H, W in that order and apply the linear classifier."""
    return dense(x.reshape(x.shape[0], -1), w, mode)
