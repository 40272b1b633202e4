"""Coded distributed 2D convolution (paper §II-B, Fig. 2).

Pipeline for one type-1 layer:

    split (eqs. 1-2)  ->  encode (eq. 3)  ->  n parallel conv subtasks
    ->  any-sufficient-subset decode (eq. 4)  ->  width-concat (+ remainder)

Convolution is linear in its input, so f(G x) = G f(x) row-wise and the
decode recovers the *exact* uncoded output (up to f32 roundoff of the
decode solve) — inference quality is unchanged (§II-B.4).

The pipeline is written against the :class:`~repro.core.schemes.CodingScheme`
protocol: any registered scheme (MDS, replication, LT, uncoded) slots in.
The coded layout — encode the k partitions into n pieces, decode the
arrived pieces back — is ``schemes.encode_pieces``/``decode_pieces``; MDS
and LT route their encode/decode GEMMs through the Pallas kernels
(kernels/mds_encode.py, kernels/mds_decode.py).

* ``run_segment``   — a coded segment: encode once, a chain of convs per
                      piece, decode once (core/netplan.py's execution
                      form); functional, or on a ``repro.dist.CodedExecutor``
                      that decodes at the k-th *arrival* — stragglers are
                      cancelled, failures re-dispatched (DESIGN.md §7).
* ``coded_conv2d``  — one coded conv: a depth-1 ``run_segment``, or with
                      ``executor=`` the backend's ``run_op`` (the threaded
                      pool, or the mesh's one SPMD program, DESIGN.md §13).
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..telemetry.trace import count, counting, span
from .schemes import (CodingScheme, chunk_bounds, commutes_elementwise,
                      decode_pieces, encode_pieces, resolve_subset,
                      source_of_piece)
from .splitting import (ChainPlan, ConvSpec, SegmentSplitPlan,
                        plan_segment_split, plan_width_split)

__all__ = [
    "conv2d",
    "conv2d_chunked",
    "coded_conv2d",
    "run_segment",
    "boundary_op_counter",
    "ACTIVATIONS",
    "add_bias",
]


# ---------------------------------------------------------------------------
# boundary-op accounting: how many master encode/decode operations ran
# ---------------------------------------------------------------------------
# The netplan claim ("2·segments coding ops instead of 2·L") is enforced by
# tests counting the operations the execution layer ACTUALLY performs, not
# what the plan promises.  Selection schemes' encode/decode are flop-free
# gathers but are still boundary operations (a master round-trip each), so
# they count too.  Every coded pipeline counts them as the telemetry
# counters ``encodes`` / ``decodes`` (telemetry/trace.py).

@contextlib.contextmanager
def boundary_op_counter():
    """Count master-side encode/decode boundary operations in this thread.

    Yields a dict ``{"encode": int, "decode": int}`` updated in place by
    every coded pipeline run (per-layer or segment) entered under the
    context: a view of the ``encodes`` / ``decodes`` counters on this
    thread.  It works outside any request too (the serving FFN, a
    directly driven ``run_segment``), so it is a :func:`counting` view,
    not a read of one request's record.
    """
    with counting({"encodes": "encode", "decodes": "decode"}) as ops:
        yield ops


def _encode_span(scheme: CodingScheme, nbytes: int) -> span:
    """Count one encode boundary (``encodes``) and return its
    ``model.encode`` span; its ``segment`` arg is the request's encode
    ordinal, its ``bytes`` those handed to the n pieces."""
    return span("model.encode", segment=count("encodes"), n=scheme.n,
                k=scheme.k, bytes=nbytes)


ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": jax.nn.relu,
    # the exact (erf) form, as ConvNeXt and the original GELU define it
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "silu": jax.nn.silu,
}


def conv2d(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """Plain VALID conv (input is pre-padded, as in the paper). NCHW/OIHW.

    The channel groups follow from the shapes: an OIHW weight whose ``I``
    is ``C_in / g`` makes a grouped conv of g groups (``I == 1``: depthwise);
    a dense weight gives g = 1, the plain conv."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=x.shape[1] // w.shape[1],
    )


def add_bias(y: jax.Array, b: jax.Array | None) -> jax.Array:
    """Add a per-output-channel bias to an NCHW conv output (None: none)."""
    return y if b is None else y + b[:, None, None]


def conv2d_chunked(x: jax.Array, w: jax.Array, stride: int = 1,
                   chunks: int = 1) -> jax.Array:
    """VALID conv computed in ``chunks`` output-column blocks (streamed
    scatter, DESIGN.md §11): block [a, b) consumes input columns
    [a*stride, (b-1)*stride + K_W), so compute on the first shipped entry
    chunk starts while the rest is still in flight.  Output columns are the
    same reductions over the same values as the one-shot conv — the result
    is identical; only the evaluation order is tiled."""
    k_w = w.shape[-1]
    w_out = (x.shape[-1] - k_w) // stride + 1
    c = max(1, min(int(chunks), int(w_out)))
    if c <= 1:
        return conv2d(x, w, stride)
    outs = [conv2d(x[..., a * stride:(b - 1) * stride + k_w], w, stride)
            for a, b in chunk_bounds(w_out, c)]
    return jnp.concatenate(outs, axis=-1)


@jax.jit
def _join_parts(parts: jax.Array) -> jax.Array:
    """The k decoded parts ``(k, B, C, H, W_p)`` side by side along W:
    ``(B, C, H, k * W_p)`` in one program (the segment exit's join)."""
    return jnp.concatenate(list(parts), axis=-1)


def _piece_chains(scheme: CodingScheme, split: SegmentSplitPlan
                  ) -> list[ChainPlan]:
    """The chain each of the n pieces runs.  A selection piece carries its
    source partition's slice verbatim (edge chains are narrower); a linear
    mix's pieces all share partition 0's geometry."""
    if commutes_elementwise(scheme):
        return [split.parts[source_of_piece(scheme, i)]
                for i in range(scheme.n)]
    return [split.parts[0]] * scheme.n


def _entry_pieces(x: jax.Array, scheme: CodingScheme,
                  split: SegmentSplitPlan) -> tuple[jax.Array, ...]:
    """The n pieces' entry inputs: the encode of the k partitions' entry
    windows along W (``schemes.encode_pieces``)."""
    return encode_pieces(scheme, x, split.windows, -1)


def coded_conv2d(
    x: jax.Array,
    w: jax.Array,
    code: CodingScheme,
    spec: ConvSpec,
    subset: Sequence[int] | None = None,
    executor=None,
    assignment: Sequence[int] | None = None,
) -> jax.Array:
    """Full coded pipeline; returns the exact conv output f(x).

    ``code`` is any registered scheme instance (MDS, replication, LT,
    uncoded).  ``subset`` is the index set S of the fastest workers whose
    outputs decoding consumes — the others are stragglers whose results are
    discarded, which we emulate by simply not consuming them.  It may hold
    more than k indices for schemes that need extra symbols (LT); ``None``
    means the scheme's canonical decodable subset.  Without an executor
    this is a depth-1 :func:`run_segment`.

    With ``executor`` (an ``ExecBackend``, dist/backend.py) the subset is
    not chosen up front: the backend runs the op (``run_op``) and decodes
    from the first decodable *arrivals* (``executor.last_report`` has the
    evidence).  ``assignment`` optionally gives per-worker piece counts
    (``hetero.allocate_pieces``); ``subset`` is ignored in this mode.
    """
    if executor is None:
        return run_segment(x, [w], code, [spec], [0], [None], subset=subset)
    from ..dist.backend import CodedOp

    plan = plan_width_split(spec, code.k)
    count("encodes")
    y_parts = executor.run_op(
        CodedOp("conv2d", code, x, w, spec=spec, assignment=assignment))
    count("decodes")

    # Reassemble on the width dim; master-kept remainder (footnote 2).
    with span("model.decode", n=code.n, k=code.k):
        y = _join_parts(y_parts)
    if plan.remainder is not None:
        with span("model.remainder"):
            r = plan.remainder
            y_rem = conv2d(x[..., r.a_i : r.b_i], w, spec.stride)
            y = jnp.concatenate([y, y_rem], axis=-1)
    return y


def _chain(xp: jax.Array, cp: ChainPlan, weights: Sequence[jax.Array],
           specs: Sequence[ConvSpec], pads: Sequence[int],
           acts: Sequence[str | None], biases: Sequence[jax.Array | None],
           apply_acts: bool, entry_chunks: int = 1) -> jax.Array:
    """Run one partition's self-contained conv chain on its (coded or true)
    entry slice.  Interior boundaries re-apply the bias and the activation
    (when ``apply_acts``: the slice holds true values) and inject the
    re-pad: full zero rows on H, and on W only the per-partition edge
    shortfall (``ChainStep.lz``/``rz``) — the interior halo columns are
    real data already resident in the slice.  ``entry_chunks > 1`` tiles
    layer 0's conv over output-column blocks (streamed entry: compute
    starts on the first shipped chunk) — identical values, tiled
    evaluation order."""
    for j, (w, sp) in enumerate(zip(weights, specs)):
        if j > 0:
            st = cp.steps[j]
            if apply_acts:
                xp = add_bias(xp, biases[j - 1])
                if acts[j - 1] is not None:
                    xp = ACTIVATIONS[acts[j - 1]](xp)
            p = int(pads[j])
            if p or st.lz or st.rz:
                xp = jnp.pad(xp, ((0, 0), (0, 0), (p, p), (st.lz, st.rz)))
            xp = conv2d(xp, w, sp.stride)
        else:
            xp = conv2d_chunked(xp, w, sp.stride, entry_chunks)
    return xp


def run_segment(
    x: jax.Array,
    weights: Sequence[jax.Array],
    scheme: CodingScheme,
    specs: Sequence[ConvSpec],
    pads: Sequence[int],
    acts: Sequence[str | None],
    split: SegmentSplitPlan | None = None,
    subset: Sequence[int] | None = None,
    executor=None,
    assignment: Sequence[int] | None = None,
    stream_chunks: int | None = None,
    biases: Sequence[jax.Array | None] | None = None,
) -> jax.Array:
    """Execute a coded *segment*: encode once, per-piece conv chains, decode
    once (core/netplan.py's execution form).

    ``stream_chunks`` (``SegmentStep.chunks`` from the plan compiler)
    streams the scatter/gather in that many column chunks: layer-0 compute
    is tiled per shipped entry chunk and the exit decode runs incrementally
    per column block at the k-th arrival (``schemes.decode_blocks`` — the
    decode-matrix solve is shared, only the skinny GEMM is chunked).  The
    decoded output is identical to the unstreamed run; the virtual-time win
    comes from the delay model's pipelined chunk timeline
    (``dist.SegmentDelay(chunks=...)``).

    ``x`` is the segment's pre-padded entry input (the caller applies layer
    0's pad, exactly as ``coded_conv2d`` expects).  ``acts[j]`` names the
    elementwise activation after layer j; interior activations run inside
    the worker chains — which is only exact for selection-structured
    schemes (``schemes.commutes_elementwise``), so a linear-mix scheme
    with an interior activation or re-pad is rejected loudly rather than
    silently producing wrong output.  The final activation is NOT applied
    here: the master applies it after decode (with any pooling), so a
    depth-1 segment is ``coded_conv2d``'s functional form.
    ``biases[j]`` (None: no bias) is layer j's per-channel bias, affine
    as an activation is elementwise: the code mixes only the linear conv,
    interior biases run in the chains under selection schemes only, and
    the last layer's is the master's, after decode.

    The entry encode runs as compiled programs with static slice bounds
    (``_entry_pieces``: ``schemes.encode_pieces``) and counts
    ``encodes_compiled``.  The exit decode does too: the stack and flatten
    of the arrived pieces, the scheme's decode GEMM, the unflatten
    (``schemes.decode_pieces``, in the executor's ``result`` or here) and
    the join along W (``_join_parts``); it counts ``decodes_compiled``.

    Functional form computes the decoded subset's chains; with
    ``executor`` (a ``repro.dist.CodedExecutor``) each chain is one
    multi-layer piece on the worker pool, decoded at the k-th *arrival*
    with straggler cancellation at segment granularity.
    """
    d = len(specs)
    if biases is None:
        biases = [None] * d
    if not (len(weights) == len(pads) == len(acts) == len(biases) == d):
        raise ValueError(f"inconsistent segment arity: {len(weights)} weights"
                         f", {d} specs, {len(pads)} pads, {len(acts)} acts, "
                         f"{len(biases)} biases")
    if split is None:
        split = plan_segment_split(specs, pads, scheme.k)
    if split.k != scheme.k:
        raise ValueError(f"split.k={split.k} != scheme.k={scheme.k}")
    commuting = commutes_elementwise(scheme)
    if not commuting and d > 1:
        if any(a is not None for a in acts[:-1]) or any(
                b is not None for b in biases[:-1]):
            raise ValueError(
                f"scheme {getattr(scheme, 'scheme_name', scheme)} is a "
                "linear mix: relu(G x) != G relu(x) and G x + b != G (x + b),"
                " so pieces cannot stay resident across an interior "
                "activation or bias — recompile with a decode point there "
                "(netplan places it automatically)")
        if any(int(p) != 0 for p in pads[1:]) or not split.uniform:
            raise ValueError(
                "interior re-padding injects partition-dependent edge zeros"
                " that a linear mix cannot represent piece-locally — only "
                "selection schemes (replication/uncoded) may fuse across it")

    piece_part = _piece_chains(scheme, split)
    width = sum(cp.entry.b_i - cp.entry.a_i for cp in piece_part)
    with _encode_span(scheme, width * math.prod(x.shape[:-1])
                      * x.dtype.itemsize):
        piece_in = _entry_pieces(x, scheme, split)
        count("encodes_compiled")
    chunks = max(1, int(stream_chunks)) if stream_chunks else 1

    def _piece(i: int) -> jax.Array:
        return _chain(piece_in[i], piece_part[i], weights, specs, pads, acts,
                      biases, apply_acts=commuting, entry_chunks=chunks)

    if executor is not None:
        if hasattr(executor, "ensure_armed"):
            # per-layer telemetry: a depth-d chain piece reports d stage
            # durations; declaring the per-layer sizes lets an adaptive
            # executor feed each stage to the estimator (DESIGN.md §8/§9)
            from .netplan import segment_layer_sizes

            executor.ensure_armed(segment_layer_sizes(specs, pads, scheme,
                                                      split))
        y_parts = executor.run(
            scheme, [lambda i=i: _piece(i) for i in range(scheme.n)],
            assignment=assignment, decode_chunks=chunks,
        )  # (k, B, C_O, H_O, W_O^p)
    else:
        subset = resolve_subset(scheme, subset)
        with span("model.local"):
            outs = [_piece(i) for i in subset]
        with span("model.decode", n=scheme.n, k=scheme.k,
                  pieces=len(subset)):
            y_parts = decode_pieces(scheme, subset, outs, chunks=chunks)
    count("decodes")
    count("decodes_compiled")

    with span("model.decode", n=scheme.n, k=scheme.k):
        y = _join_parts(y_parts)
    if split.remainder is not None:
        # footnote 2 at segment granularity: the master runs the remainder
        # columns' whole chain locally, on true values (acts always apply)
        with span("model.remainder"):
            y_rem = _chain(
                x[..., split.remainder.entry.a_i:split.remainder.entry.b_i],
                split.remainder, weights, specs, pads, acts, biases,
                apply_acts=True)
            y = jnp.concatenate([y, y_rem], axis=-1)
    return y

