"""Operations and bytes of the benchmark's kernels, from shapes alone.

Every count follows the algorithm (a VALID conv, a dense product, the
coding GEMMs of eqs. 3-4), never a kernel's implementation, so that a
change to a kernel cannot make a count stale.  Bytes are the operands read
and the result written once each, at ``itemsize`` bytes per element.
"""
from __future__ import annotations

from math import prod


def conv_flops(x_shape, w_shape, stride: int) -> int:
    """2 * B * C_out * H_out * W_out * C_in * K_h * K_w for a VALID NCHW conv
    of an input ``x_shape`` (B, C_in, H, W) by an OIHW ``w_shape``."""
    b, c_in, h, w = x_shape
    c_out, c_in_w, k_h, k_w = w_shape
    assert c_in == c_in_w, (x_shape, w_shape)
    h_out, w_out = (h - k_h) // stride + 1, (w - k_w) // stride + 1
    return 2 * b * c_out * h_out * w_out * c_in * k_h * k_w


def conv_bytes(x_shape, w_shape, stride: int, itemsize: int = 4) -> int:
    b, _, h, w = x_shape
    c_out, _, k_h, k_w = w_shape
    out = b * c_out * ((h - k_h) // stride + 1) * ((w - k_w) // stride + 1)
    return itemsize * (prod(x_shape) + prod(w_shape) + out)


def gemm_flops(m: int, k: int, f: int) -> int:
    """(m, k) @ (k, f): the coding GEMMs (G @ X encode, D @ Y decode)."""
    return 2 * m * k * f


def gemm_bytes(m: int, k: int, f: int, itemsize: int = 4) -> int:
    return itemsize * (m * k + k * f + m * f)


def model_flops(layers, batch: int, head_features: int, classes: int) -> int:
    """Uncoded forward FLOPs of a conv stack plus its linear head; ``layers``
    as the references list them (padded input size, kernel, stride)."""
    total = 0
    for li in layers:
        size = li["size"] + 2 * li["pad"]
        total += conv_flops((batch, li["c_in"], size, size),
                            (li["c_out"], li["c_in"], li["kernel"],
                             li["kernel"]), li["stride"])
    return total + 2 * batch * head_features * classes
