"""MeshExecutor: k-of-n coded dispatch as one ``shard_map`` program.

The second implementation of the :mod:`repro.dist.backend` seam.  Where
``CodedExecutor`` runs pieces on threads against a (mostly virtual)
clock, ``MeshExecutor`` maps each coded piece to one slice of the mesh's
``model`` axis (launch/mesh.py) and compiles

    encode  ->  per-slice shard GEMM / conv  ->  masked gather  ->  decode

into a single SPMD program per (op shape, scheme, fault pattern):

* **encode** — each slice holds its own generator row and computes its
  piece with the Pallas skinny-GEMM kernel (kernels/mds_encode.py);
  selection schemes (replication/uncoded) carry a per-slice source index
  and gather instead, so copies are bit-exact (a 0/1 matrix encode would
  rewrite ``-0.0`` to ``+0.0``).  This per-slice encode is the one coded
  layout outside ``core.schemes.encode_pieces``: each slice computes one
  generator row, not the master's whole (n, F) GEMM.
* **shard compute** — the piece GEMM runs through the same Pallas kernel
  (``skinny_gemm_pallas``); the piece conv is the identical
  ``lax.conv`` the threaded backend's thunks call, so both backends
  produce bit-identical piece values.
* **decode** — the master gathers the decodable subset and runs the
  Pallas decode GEMM (kernels/mds_decode.py, via
  ``core.schemes.decode_blocks``) as a *column-parallel* second
  ``shard_map`` when the flattened feature dim tiles the axis — every
  slice recovers its own block of all k sources (eq. 4) — falling back
  to a replicated decode otherwise.

k-of-n semantics under SPMD (DESIGN.md §13): a shard_map program cannot
cancel a lane — every slice runs to completion on real hardware.  "Early
exit" is therefore *algebraic*, not temporal: dead/unfinished slices'
contributions are multiplied by a 0.0 mask and never gathered; the
decodable subset is chosen ahead of dispatch from the executor's
configured fault pattern (``order``/``dead``/``stragglers``), exactly the
subset the threaded backend's k-th-arrival rule would consume under the
same pattern.  A dead slice's piece is modeled as *redispatched*: it
re-enters the arrival order at the very end (after stragglers), so
schemes that need every piece (uncoded) still decode — matching the
thread pool, whose failed pieces are re-run on surviving workers.
"""
from __future__ import annotations

import contextlib
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.schemes import commutes_elementwise, decode_blocks, source_of_piece
from ..kernels.mds_encode import skinny_gemm_pallas
from ..kernels.ops import interpret_default, mds_encode
from ..launch.mesh import MODEL_AXIS, PiecePlacementError, make_local_mesh, \
    validate_pieces
from ..launch.sharding import decode_block_spec, piece_spec
from .clock import RealClock
from .executor import decodable_prefix
from .pool import Arrival, RunReport, Undecodable

__all__ = ["MeshExecutor"]


class _MeshFleet:
    """The pool-shaped facade the serving stack expects on a backend.

    The scheduler scripts faults/delays and reads counters through
    ``executor.pool``; on a mesh there is no thread pool, so this object
    carries the counters and accepts (and ignores) the scripting fields.
    Membership is the mesh itself: workers are the ``axis`` slices.
    """

    def __init__(self, mesh: jax.sharding.Mesh, axis: str):
        self.mesh = mesh
        self.axis = axis
        self.clock = RealClock()
        self.fault_plan = None   # assignable: scheduler _arm_step writes it
        self.delay_model = None  # assignable: scheduler reseeds it
        self.dispatch_count = 0

    def alive_workers(self) -> list[int]:
        return list(range(int(self.mesh.shape[self.axis])))

    def dispatch_preview(self) -> list[int]:
        return self.alive_workers()

    @contextlib.contextmanager
    def group(self):
        yield self

    def close(self) -> None:
        pass


def _scheme_key(scheme) -> tuple:
    return (type(scheme).__name__, scheme.n, scheme.k,
            getattr(scheme, "node_kind", None),
            getattr(scheme, "seed", None), getattr(scheme, "c", None),
            getattr(scheme, "delta", None))


def _generator(scheme, dtype) -> np.ndarray:
    """The (n, k) encode matrix, bit-identical to what ``scheme.encode``
    applies: extracted by encoding the identity (each coded row of I picks
    out generator entries exactly — unit-vector dot products are exact)."""
    eye = jnp.eye(scheme.k, dtype=dtype)
    return np.asarray(scheme.encode(eye))


class MeshExecutor:
    """Coded dispatch on a JAX device mesh (the ``ExecBackend`` seam).

    Parameters
    ----------
    mesh:
        A mesh with the worker axis (default: ``make_local_mesh()``, all
        local devices on ``model``).
    axis:
        Which mesh axis the pieces tile.
    order / dead / stragglers:
        The modeled fault pattern (DESIGN.md §13): ``order`` overrides the
        natural piece arrival order; ``dead`` pieces are redispatched (they
        arrive after everything else); ``stragglers`` arrive after all
        healthy pieces.  The decodable subset — which slices' results the
        decode consumes — is derived from this pattern with the same
        ``decodable_prefix`` rule the threaded master applies at the k-th
        arrival.
    interpret:
        Forwarded to the Pallas kernels (None = interpret on the CPU
        backend only, compiled everywhere else).

    A program is built and jitted once per (kind, scheme, shapes, dtypes,
    stride, subset) — ``compile_count`` exposes cache fills so callers can
    assert the compile-once contract.
    """

    def __init__(self, mesh: jax.sharding.Mesh | None = None, *,
                 axis: str = MODEL_AXIS,
                 order: Sequence[int] | None = None,
                 dead: Sequence[int] = (),
                 stragglers: Sequence[int] = (),
                 interpret: bool | None = None):
        self.mesh = mesh if mesh is not None else make_local_mesh()
        if axis not in self.mesh.shape:
            raise PiecePlacementError(
                f"mesh has no {axis!r} axis (axes: "
                f"{tuple(self.mesh.axis_names)})")
        self.axis = axis
        self.order = None if order is None else tuple(int(p) for p in order)
        self.dead = tuple(int(p) for p in dead)
        self.stragglers = tuple(int(p) for p in stragglers)
        self.interpret = interpret_default(interpret)
        self.pool = _MeshFleet(self.mesh, axis)
        self.elastic = False
        self.run_count = 0
        self.last_report: RunReport | None = None
        self.on_report = None
        # optional telemetry.TraceSink.  A shard_map program has no
        # per-piece timeline — the mesh emits run-level spans ONLY (the
        # honest degradation DESIGN.md §15 documents), on real wall time.
        self.trace_sink = None
        self.compile_count = 0
        self._programs: dict = {}
        self._chain_t = 0.0

    # -- executor contract (dist/backend.py) --------------------------------
    def close(self) -> None:
        self._programs.clear()

    def __enter__(self) -> "MeshExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @contextlib.contextmanager
    def chain(self, start: float = 0.0):
        """Causal-chain marker for API parity: SPMD runs are synchronous,
        so successive run_ops are already serial; nothing to gate."""
        prev = self._chain_t
        self._chain_t = float(start)
        try:
            yield self
        finally:
            self._chain_t = prev

    def ensure_armed(self, sizes) -> None:
        """Telemetry hook — nothing to arm (no delay model to train)."""

    def plan_matmul(self, scheme, scheme_name: str, n_tokens: int,
                    d_in: int, d_out: int):
        """No re-planning: mesh membership is fixed at construction."""
        return None, None, None

    def run(self, scheme, piece_fns, **kw):
        raise NotImplementedError(
            "MeshExecutor executes whole coded ops (run_op), not opaque "
            "piece thunks — a thunk hides the math shard_map must trace. "
            "Segment chains and hand-built piece functions need the "
            "threaded CodedExecutor backend.")

    # -- fault pattern -> decodable subset ----------------------------------
    def _arrival_order(self, n: int) -> list[int]:
        order = (list(self.order) if self.order is not None
                 else list(range(n)))
        if sorted(order) != list(range(n)):
            raise ValueError(
                f"order must be a permutation of range({n}), got {order}")
        dead = {p for p in self.dead if p < n}
        slow = {p for p in self.stragglers if p < n} - dead
        healthy = [p for p in order if p not in dead and p not in slow]
        # stragglers arrive after every healthy piece; dead pieces are
        # redispatched and arrive last of all (thread-pool semantics)
        return (healthy + [p for p in order if p in slow]
                + [p for p in order if p in dead])

    def _subset(self, scheme) -> tuple[int, ...]:
        sub = decodable_prefix(scheme, self._arrival_order(scheme.n))
        if sub is None:
            raise Undecodable(
                f"{type(scheme).__name__}(n={scheme.n}, k={scheme.k}) "
                f"cannot decode under dead={self.dead} "
                f"stragglers={self.stragglers} on this mesh")
        return tuple(int(p) for p in sub)

    # -- program construction ------------------------------------------------
    def _build(self, op, subset: tuple[int, ...]):
        scheme, ndev = op.scheme, int(self.mesh.shape[self.axis])
        n, k = scheme.n, scheme.k
        axis, mesh = self.axis, self.mesh
        interpret = self.interpret
        # masked/zeroed contributions: slices whose piece is not consumed
        # (beyond-n padding, dead-before-redispatch, stragglers past the
        # k-th arrival) contribute exact zeros to the gathered stack
        mask = np.zeros((ndev,), np.float32)
        mask[list(subset)] = 1.0
        mask = jnp.asarray(mask)
        selection = commutes_elementwise(scheme)
        if selection:
            src = np.zeros((ndev,), np.int32)
            for p in range(n):
                src[p] = source_of_piece(scheme, p)
            src = jnp.asarray(src)
        else:
            G = _generator(scheme, op.x.dtype)
            Gp = np.zeros((ndev, k), G.dtype)
            Gp[:n] = G
            Gp = jnp.asarray(Gp)

        if op.kind == "matmul":
            compute = lambda piece, w: skinny_gemm_pallas(
                piece, w, interpret=interpret)
        else:
            from ..core.coded_conv import conv2d

            stride = op.spec.stride
            compute = lambda piece, w: conv2d(piece, w, stride)

        def worker(enc, m, x, w):
            # x: the replicated (k,) + source-shape stack
            if selection:
                piece = jnp.take(x, enc[0], axis=0)
            else:
                piece = mds_encode(enc, x.reshape(k, -1), interpret=interpret
                                   ).reshape(x.shape[1:])
            y = compute(piece, w)
            return (y * m[0].astype(y.dtype))[None]

        # piece-stacked output rank equals the source-stacked input rank:
        # (k, t_p, d_in) -> (ndev, t_p, d_out); (k,N,C,H,Wp) -> (ndev,N,O,H',Wp')
        enc_arg = src if selection else Gp
        fan_out = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=piece_spec(op.x.ndim + 1, axis), check_vma=False)
        sub_idx = jnp.asarray(list(subset), jnp.int32)
        subset_l = list(subset)

        def sharded_decode(stacked):
            """Column-parallel decode: every slice recovers its own block
            of all k sources (the sharded skinny GEMM of eq. 4)."""
            spec = decode_block_spec(stacked.ndim, axis)
            return jax.shard_map(
                lambda blk: decode_blocks(scheme, subset_l, blk),
                mesh=mesh, in_specs=(spec,), out_specs=spec,
                check_vma=False)(stacked)

        windows, src_axis = op.windows, op.axis

        def program(x, w):
            sources = jnp.stack([jax.lax.slice_in_dim(x, a, b, axis=src_axis)
                                 for a, b in windows])
            pieces = fan_out(enc_arg, mask, sources, w)
            gathered = jnp.take(pieces, sub_idx, axis=0)
            if gathered.shape[-1] % ndev == 0:
                return sharded_decode(gathered)
            return decode_blocks(scheme, subset_l, gathered)

        return jax.jit(program)

    def _key(self, op, subset: tuple[int, ...]) -> tuple:
        stride = op.spec.stride if op.spec is not None else None
        return (op.kind, _scheme_key(op.scheme), tuple(op.x.shape),
                str(op.x.dtype), tuple(op.w.shape), str(op.w.dtype),
                stride, op.windows, subset)

    def run_op(self, op) -> jax.Array:
        """Run one coded op end-to-end on the mesh; return the decoded
        (k,)+piece-shape stack.  Wall-clock (``RunReport.wall_s`` ==
        ``t_complete``: there is no virtual plane) is real device time —
        the program blocks until the decoded result is materialized."""
        scheme = op.scheme
        validate_pieces(self.mesh, scheme.n, axis=self.axis)
        subset = self._subset(scheme)
        key = self._key(op, subset)
        prog = self._programs.get(key)
        if prog is None:
            prog = self._build(op, subset)
            self._programs[key] = prog
            self.compile_count += 1
        t0 = time.perf_counter()
        out = jax.block_until_ready(prog(op.x, op.w))
        wall = time.perf_counter() - t0
        self._book(scheme, subset, wall)
        return out

    def _book(self, scheme, subset: tuple[int, ...], wall: float) -> None:
        n = scheme.n
        dead = {p for p in self.dead if p < n}
        report = RunReport(
            t_complete=wall, wall_s=wall, subset=list(subset),
            arrivals=[Arrival(worker=p, piece=p, t=wall) for p in subset],
            failures=[(p, 0.0) for p in sorted(dead)],
            redispatched=[(p, p, p) for p in sorted(dead) if p in subset],
            cancelled=[p for p in range(n)
                       if p not in subset and p not in dead],
            assignment={p: p for p in range(n)},
            t_submit=self._chain_t)
        self.pool.dispatch_count += n + sum(1 for p in dead if p in subset)
        self.run_count += 1
        self.last_report = report
        if self.trace_sink is not None:
            from ..telemetry.trace import Span
            origin = float(getattr(self.trace_sink, "origin", 0.0))
            self.trace_sink.span(Span(
                "run", "exec", origin + self._chain_t, wall, "mesh",
                {"n": n, "k": scheme.k, "pieces": len(report.assignment),
                 "redispatches": len(report.redispatched),
                 "decoded": len(report.subset)}))
        if self.on_report is not None:
            self.on_report(report)
