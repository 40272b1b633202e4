"""The system under test for the CNN configurations: the program's coded
forward (``repro.models.cnn``) on a real-clock ``CodedExecutor`` pool.

The benchmark makes the weights; this adapter hands them to the program in
its own layout, drives one forward per request, sets each request's fault
plan on the shared pool, and keeps what each ``RunReport`` says of its run.  Counting
hooks (``count_work``) record the shapes of the convs and coding GEMMs one
forward executes, so that ``bench/counts.py`` can count their work.
"""
from __future__ import annotations

import contextlib
import importlib
import time


def _resolve(path: str):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def _executor_class():
    import jax

    from repro.dist import CodedExecutor

    class BenchExecutor(CodedExecutor):
        """A ``CodedExecutor`` whose runs are host spans in a traced run."""

        annotate = False

        def run(self, scheme, piece_fns, **kw):
            if not self.annotate:
                return super().run(scheme, piece_fns, **kw)
            with jax.profiler.TraceAnnotation("bench.executor_run"):
                return super().run(scheme, piece_fns, **kw)

    return BenchExecutor


class CodedCNN:
    """One configuration's coded forward, ready to serve requests."""

    def __init__(self, cfg: dict, params: dict):
        from repro.dist import RealClock

        coding = cfg["coding"]
        self.n = int(coding["n_workers"])
        self.scheme = coding["scheme"]
        self._entry = _resolve(cfg["entry"])
        specs = _resolve(cfg["conv_specs"])
        names = [li.name for li in specs(cfg["image"])]
        self.params = {"convs": [params[name] for name in names],
                       "head": params["head"]}
        self.executor = _executor_class()(self.n, clock=RealClock())
        # (pieces dispatched, pieces decoded, wall seconds) of every run
        self.reports: list[tuple[int, int, float]] = []
        self.executor.on_report = lambda r: self.reports.append(
            (len(r.assignment), len(r.subset), r.wall_s))

    def set_fault(self, straggler: int | None, slowdown: float) -> None:
        from repro.dist import FaultPlan

        self.executor.pool.fault_plan = (
            FaultPlan() if straggler is None
            else FaultPlan(straggler={int(straggler): float(slowdown)}))

    def annotate(self, on: bool) -> None:
        self.executor.annotate = bool(on)

    def forward(self, x):
        return self._entry(self.params, x, scheme=self.scheme, n=self.n,
                           executor=self.executor)

    def warm(self, x) -> dict:
        """Compile the forward's programs on this thread first: one
        functional coded forward (no pool), so that the pool's n workers
        find every piece program compiled instead of each compiling it."""
        t0 = time.perf_counter()
        self._entry(self.params, x, scheme=self.scheme,
                    n=self.n).block_until_ready()
        return {"functional_forward_s": time.perf_counter() - t0}

    def close(self) -> None:
        self.executor.close()

    @staticmethod
    def count_work():
        return count_work()


@contextlib.contextmanager
def count_work():
    """Record the shapes of every conv and coding GEMM the program runs
    inside the block, from any thread: yields a dict of lists
    ``conv``: (x_shape, w_shape, stride), ``encode``/``decode``: (m, k, f)."""
    import repro.core.coded_conv as coded_conv
    import repro.kernels.ops as ops
    import repro.models.cnn as cnn

    seen = {"conv": [], "encode": [], "decode": []}
    conv0, enc0, dec0 = coded_conv.conv2d, ops.mds_encode, ops.mds_decode

    def conv(x, w, stride=1):
        seen["conv"].append((tuple(x.shape), tuple(w.shape), int(stride)))
        return conv0(x, w, stride)

    def gemm(kind, fn):
        def wrapped(a, x, **kw):
            seen[kind].append((a.shape[0], a.shape[1], x.shape[1]))
            return fn(a, x, **kw)
        return wrapped

    coded_conv.conv2d = cnn.conv2d = conv
    ops.mds_encode, ops.mds_decode = gemm("encode", enc0), gemm("decode", dec0)
    try:
        yield seen
    finally:
        coded_conv.conv2d = cnn.conv2d = conv0
        ops.mds_encode, ops.mds_decode = enc0, dec0


def build(cfg: dict, params: dict) -> CodedCNN:
    return CodedCNN(cfg, params)
