# Execution-backend seam: the contract the coded call sites rely on.
#
# ``coded_matmul`` / ``coded_conv2d`` (core/), the model's ``plan_matmul``
# hook (models/model.py), and the serving stack (serving/engine.py,
# serving/scheduler.py) never cared that pieces ran on threads — they need
# a *plan* (how many pieces, which worker gets which), a way to *run* one
# coded op to its decoded output, and a *report sink* (``on_report`` /
# ``last_report`` / ``run_count``) for telemetry.  This module names that
# contract so a second implementation — ``dist/mesh_exec.MeshExecutor``,
# which runs the same op as one ``shard_map`` program over a JAX device
# mesh — can slot in behind one constructor argument.
#
# Backends:
#   * ``dist.executor.CodedExecutor`` (+ ``AdaptiveExecutor``): the
#     reference threaded backend.  Real k-of-n semantics — the master
#     returns at the k-th arrival and cancels stragglers.
#   * ``dist.mesh_exec.MeshExecutor``: every piece is a slice of the
#     ``model`` mesh axis; encode → shard GEMM/conv → decode compile to a
#     single SPMD program (see DESIGN.md §13 for what "early exit" means
#     when nobody can actually cancel a shard).
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Mapping, Protocol, Sequence, runtime_checkable

import jax

from ..core.splitting import ConvSpec, plan_token_split, plan_width_split

__all__ = ["CodedOp", "ExecBackend"]


@dataclass(frozen=True)
class CodedOp:
    """One coded operator, backend-agnostically described.

    ``x`` is the op's whole input; its k sources are the static
    ``windows`` of one axis (``axis``), and the master keeps what lies
    past them (the remainder, footnote 2).  ``kind`` selects the math:
      * ``"matmul"``: ``x`` is (T, d_in) tokens, the sources are its k
        token blocks (the first axis) and ``w`` is (d_in, d_out); piece i
        computes ``pieces[i] @ w``.
      * ``"conv2d"``: ``x`` is the padded (N, C, H, W) input, the sources
        are its k width partitions, halos included (the last axis), and
        ``w`` is OIHW; piece i computes ``conv2d(pieces[i], w,
        spec.stride)``.

    The decoded result a backend must return is the (k,) + piece-shape
    stack of recovered source outputs — exactly what
    ``core.schemes.decode_pieces`` yields from the first decodable subset.
    """

    kind: str
    scheme: Any
    x: jax.Array
    w: jax.Array
    spec: ConvSpec | None = None
    assignment: Mapping[int, int] | Sequence[int] | None = None
    decode_chunks: int = 1

    def __post_init__(self) -> None:
        if self.kind not in ("matmul", "conv2d"):
            raise ValueError(f"unknown CodedOp kind: {self.kind!r}")
        if self.kind == "conv2d" and self.spec is None:
            raise ValueError("conv2d CodedOp requires a ConvSpec")

    @property
    def axis(self) -> int:
        """The axis the sources tile: tokens first, width last."""
        return 0 if self.kind == "matmul" else -1

    @property
    def windows(self) -> tuple[tuple[int, int], ...]:
        """The k sources' static ``[a, b)`` ranges along ``axis``."""
        if self.kind == "matmul":
            return plan_token_split(self.x.shape[0], self.scheme.k).windows
        return plan_width_split(self.spec, self.scheme.k).windows


@runtime_checkable
class ExecBackend(Protocol):
    """What a coded-dispatch backend must provide.

    Attributes (telemetry surface; ``ServingScheduler`` reads all three):
      * ``run_count``: decoded runs completed so far.
      * ``last_report``: the most recent ``RunReport`` (or ``None``).
      * ``on_report``: optional callback fired with each ``RunReport``.

    Structural extras the serving stack leans on — a ``pool`` facade with
    ``clock`` / ``delay_model`` / ``fault_plan`` / ``dispatch_count`` /
    ``alive_workers()`` / ``group()``, and a ``chain()`` context manager —
    are part of the de-facto contract; ``MeshExecutor`` provides inert
    stand-ins so schedulers run unchanged.
    """

    run_count: int
    last_report: Any
    on_report: Any

    def run_op(self, op: CodedOp) -> jax.Array:
        """Encode, dispatch, and decode one coded op; return the (k,)-stack."""
        ...

    def plan_matmul(
        self, scheme: Any, scheme_name: str, n_tokens: int, d_in: int, d_out: int
    ) -> tuple[int | None, int | None, Any]:
        """Optionally re-plan (n, k, assignment) for an upcoming GEMM."""
        ...

    def ensure_armed(self, sizes: Sequence[int]) -> None:
        """Hint the per-segment piece sizes of an upcoming chained run."""
        ...

    def close(self) -> None: ...

