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
from jax.sharding import PartitionSpec as P

from ..telemetry.trace import count
from .schemes import (CodingScheme, commutes_elementwise, resolve_subset,
                      source_of_piece)
from .splitting import SplitPlan, plan_token_split

__all__ = ["coded_matmul", "coded_matmul_sharded", "coded_ffn_segment"]


def _encode_tokens(code: CodingScheme, x: jax.Array, plan: SplitPlan) -> jax.Array:
    """(T, d) tokens -> (n, T_p, d) coded token slices."""
    k = code.k
    t_p = plan.w_out_p
    parts = x[: k * t_p].reshape(k, t_p, -1)
    flat = parts.reshape(k, -1)
    return code.encode(flat).reshape(code.n, t_p, x.shape[-1])


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

    x: (T, d_in), w: (d_in, d_out).  The remainder rows (T mod k) are
    computed by the master (paper footnote 2).

    With ``executor`` (a ``repro.dist.CodedExecutor``) the n GEMM subtasks
    run on the worker pool and the decode consumes the first decodable
    arrivals; ``subset`` is ignored, ``assignment`` optionally routes
    per-worker piece counts (``hetero.allocate_pieces``).
    """
    T = x.shape[0]
    plan = plan_token_split(T, code.k)
    if executor is not None and hasattr(executor, "run_op"):
        # backend seam (dist/backend.py): hand the backend the whole op —
        # source stack + weights — so encode/shard-GEMM/decode can run
        # where the backend wants them (the thread pool encodes eagerly;
        # the mesh fuses all three into one shard_map program)
        from ..dist.backend import CodedOp

        parts = x[: code.k * plan.w_out_p].reshape(code.k, plan.w_out_p, -1)
        count("encodes")
        decoded = executor.run_op(
            CodedOp("matmul", code, parts, w, assignment=assignment))
        y = decoded.reshape(code.k * plan.w_out_p, w.shape[-1])
        count("decodes")
        if plan.remainder is not None:
            y = jnp.concatenate([y, x[plan.remainder.a_i :] @ w], axis=0)
        return y
    coded_in = _encode_tokens(code, x, plan)  # (n, T_p, d_in)
    count("encodes")
    if executor is not None:
        # legacy thunk surface: pre-seam executors and test doubles
        decoded = executor.run(
            code,
            [lambda i=i: coded_in[i] @ w for i in range(code.n)],
            assignment=assignment,
        )  # (k, T_p, d_out)
        y = decoded.reshape(code.k * plan.w_out_p, w.shape[-1])
    else:
        subset = resolve_subset(code, subset)
        coded_out = jnp.einsum("ntd,df->ntf", coded_in, w)  # n worker GEMMs
        sel = coded_out[jnp.asarray(subset)]
        decoded = code.decode_from(subset, sel.reshape(len(subset), -1))
        y = decoded.reshape(code.k * plan.w_out_p, w.shape[-1])
    count("decodes")
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
    T = x.shape[0]
    plan = plan_token_split(T, code.k)

    def chain(xt: jax.Array) -> jax.Array:
        h = xt @ w_in
        h = act(xt @ w_gate) * h if w_gate is not None else act(h)
        return h @ w_out

    t_p = plan.w_out_p
    srcs = [source_of_piece(code, i) for i in range(code.n)]
    piece_in = [x[s * t_p:(s + 1) * t_p] for s in srcs]
    count("encodes")  # the selection dispatch is the boundary op
    if executor is not None:
        decoded = executor.run(
            code, [lambda i=i: chain(piece_in[i]) for i in range(code.n)],
            assignment=assignment)
        y = decoded.reshape(code.k * t_p, w_out.shape[-1])
    else:
        subset = resolve_subset(code, subset)
        outs = jnp.stack([chain(piece_in[i]) for i in subset])
        decoded = code.decode_from(subset, outs.reshape(len(subset), -1))
        y = decoded.reshape(code.k * t_p, w_out.shape[-1])
    count("decodes")
    if plan.remainder is not None:
        y = jnp.concatenate([y, chain(x[plan.remainder.a_i:])], axis=0)
    return y


def coded_matmul_sharded(
    x: jax.Array,
    w: jax.Array,
    code: CodingScheme,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
) -> jax.Array:
    """Pod form: n coded GEMM subtasks on the ``axis`` mesh axis."""
    n = mesh.shape[axis]
    if n != code.n:
        raise ValueError(f"mesh axis {axis} has size {n}, code.n={code.n}")
    T = x.shape[0]
    plan = plan_token_split(T, code.k)
    coded_in = _encode_tokens(code, x, plan)

    @jax.jit
    def _run(coded_in, w):
        def worker(xi, w):
            return jnp.einsum("ntd,df->ntf", xi, w)

        return jax.shard_map(
            worker, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis)
        )(coded_in, w)

    coded_out = _run(coded_in, w)
    subset = code.default_subset()
    decoded = code.decode_from(
        subset, coded_out[jnp.asarray(subset)].reshape(len(subset), -1))
    y = decoded.reshape(code.k * plan.w_out_p, w.shape[-1])
    if plan.remainder is not None:
        y = jnp.concatenate([y, x[plan.remainder.a_i :] @ w], axis=0)
    return y
