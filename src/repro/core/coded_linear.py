"""Coded distributed GEMM — the transformer adaptation of CoCoI.

The paper codes 2D convolution because it is linear in its input.  A GEMM
``Y = X @ W`` is the degenerate K=S=1 case: the token dimension plays the
role of the output width, partitions are disjoint (no halo), and the same
(n, k)-MDS encode/decode applies row-exactly:

    G (X_1..X_k) @ W  =  (G X)_1..n @ W      (linearity in X)

This is what lets CoCoI act on the type-1 ops of the assigned transformer
architectures (FFN and projection GEMMs — see DESIGN.md §4).  Nonlinear ops
(softmax attention, SSM selective scan, activations) remain uncoded type-2
work, mirroring the paper's type-1/type-2 split.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..telemetry.trace import count
from .schemes import (CodingScheme, commutes_elementwise, decode_pieces,
                      encode_pieces, resolve_subset)
from .splitting import plan_token_split

__all__ = ["coded_matmul", "coded_ffn_segment"]


def coded_matmul(
    x: jax.Array,
    w: jax.Array,
    code: CodingScheme,
    subset: Sequence[int] | None = None,
    executor=None,
    assignment: Sequence[int] | None = None,
) -> jax.Array:
    """Exact Y = X @ W recovered from a decodable subset of the n coded
    worker GEMMs, under any registered scheme.

    x: (T, d_in), w: (d_in, d_out).  The k token blocks are encoded into
    the n pieces and decoded back by ``schemes.encode_pieces`` /
    ``decode_pieces``; the remainder rows (T mod k) are computed by the
    master (paper footnote 2).

    With ``executor`` (an ``ExecBackend``, dist/backend.py) the backend
    runs the whole op (``run_op``) and decodes from the first decodable
    arrivals; ``subset`` is ignored, ``assignment`` optionally routes
    per-worker piece counts (``hetero.allocate_pieces``).
    """
    plan = plan_token_split(x.shape[0], code.k)
    if executor is not None:
        from ..dist.backend import CodedOp

        count("encodes")
        decoded = executor.run_op(
            CodedOp("matmul", code, x, w, assignment=assignment))
    else:
        subset = resolve_subset(code, subset)
        pieces = encode_pieces(code, x, plan.windows, 0)
        count("encodes")
        decoded = decode_pieces(code, subset, [pieces[i] @ w for i in subset])
    count("decodes")
    y = decoded.reshape(code.k * plan.w_out_p, w.shape[-1])
    if plan.remainder is not None:
        y = jnp.concatenate([y, x[plan.remainder.a_i :] @ w], axis=0)
    return y


def coded_ffn_segment(
    x: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    act: Callable[[jax.Array], jax.Array],
    code: CodingScheme,
    w_gate: jax.Array | None = None,
    subset: Sequence[int] | None = None,
    executor=None,
    assignment: Sequence[int] | None = None,
) -> jax.Array:
    """The whole (gated) FFN as ONE coded token segment (DESIGN.md §9).

    Token slices are the K=S=1 degenerate width split: no halo at all, so
    consecutive GEMMs keep their slice resident trivially — the only
    obstacle to fusing in -> act -> (gate *) -> out into a single
    encode/decode pair is the activation, which commutes exactly with
    selection-structured schemes (replication/uncoded).  For those the
    coded-GEMM boundary count of one FFN drops from 6 (3 per-GEMM
    encode/decode pairs) to 2, and the master<->worker traffic from
    3 x (d_model + d_ff)-sized transfers to one d_model each way.  Linear
    mixes (MDS/LT) are rejected: relu(G x) != G relu(x).

    x: (T, d_model).  The T mod k remainder tokens run on the master
    through the same fused chain (footnote 2).
    """
    if not commutes_elementwise(code):
        raise ValueError(
            f"scheme {getattr(code, 'scheme_name', code)} is a linear mix: "
            "the FFN activation cannot run inside a coded token slice — "
            "use per-GEMM coded_matmul (decode before each activation)")
    plan = plan_token_split(x.shape[0], code.k)

    def chain(xt: jax.Array) -> jax.Array:
        h = xt @ w_in
        h = act(xt @ w_gate) * h if w_gate is not None else act(h)
        return h @ w_out

    pieces = encode_pieces(code, x, plan.windows, 0)
    count("encodes")  # the selection dispatch is the boundary op
    if executor is not None:
        decoded = executor.run(
            code, [lambda p=p: chain(p) for p in pieces],
            assignment=assignment)
    else:
        subset = resolve_subset(code, subset)
        decoded = decode_pieces(code, subset, [chain(pieces[i])
                                               for i in subset])
    count("decodes")
    y = decoded.reshape(code.k * plan.w_out_p, w_out.shape[-1])
    if plan.remainder is not None:
        y = jnp.concatenate([y, chain(x[plan.remainder.a_i:])], axis=0)
    return y
