"""The system under test for the ConvNeXt configurations: the program's
coded forward (``repro.models.cnn.convnext_forward``) on a real-clock
``CodedExecutor`` pool, served as ``coded_cnn`` serves the other CNNs.

It differs from ``coded_cnn`` in two things.  The weights go to the
program in ConvNeXt's layout (convs with biases, LayerNorms and layer
scales by block).  And ``count_work`` records a grouped conv of g groups
as g dense convs, one per group, so that ``bench/counts.py``'s dense counts
give its true work: a depthwise conv of C channels is C one-channel convs
``((B, 1, H, W), (1, 1, k, k), stride)``, which count its input, weight
and output once each and its FLOPs exactly.
"""
from __future__ import annotations

import contextlib

from bench.systems.coded_cnn import (CodedCNN, _executor_class, _resolve,
                                     count_work as _count_dense)


def program_params(cfg: dict, p: dict) -> dict:
    """The reference's weights by name, in ``convnext_forward``'s layout."""
    def conv(name):
        return {"w": p[f"{name}.w"], "b": p[f"{name}.b"]}

    def norm(name):
        return {"scale": p[f"{name}.scale"], "shift": p[f"{name}.shift"]}

    stages = []
    for s, depth in enumerate(cfg["depths"], start=1):
        down = None if s == 1 else dict(conv(f"ds{s}"),
                                        norm=norm(f"ds{s}.norm"))
        blocks = [{"dw": conv(f"s{s}b{b}dw"), "norm": norm(f"s{s}b{b}.norm"),
                   "pw1": conv(f"s{s}b{b}pw1"), "pw2": conv(f"s{s}b{b}pw2"),
                   "gamma": p[f"s{s}b{b}.gamma"]} for b in range(depth)]
        stages.append({"down": down, "blocks": blocks})
    return {"stem": dict(conv("stem"), norm=norm("stem.norm")),
            "stages": stages,
            "head": {"norm": norm("head.norm"), "w": p["head.w"],
                     "b": p["head.b"]}}


class CodedConvNeXt(CodedCNN):
    """One ConvNeXt configuration's coded forward, ready to serve."""

    def __init__(self, cfg: dict, params: dict):
        from repro.dist import RealClock

        coding = cfg["coding"]
        self.n = int(coding["n_workers"])
        self.scheme = coding["scheme"]
        self._entry = _resolve(cfg["entry"])
        self.params = program_params(cfg, params)
        self.executor = _executor_class()(self.n, clock=RealClock())
        # (pieces dispatched, pieces decoded, wall seconds) of every run
        self.reports: list[tuple[int, int, float]] = []
        self.executor.on_report = lambda r: self.reports.append(
            (len(r.assignment), len(r.subset), r.wall_s))

    @staticmethod
    def count_work():
        return count_work()


def per_group(conv):
    """A recorded conv ``(x_shape, w_shape, stride)`` as the dense convs of
    its groups (itself when it has one)."""
    (b, c_in, h, w), (c_out, c_in_w, k_h, k_w), stride = conv
    g = c_in // c_in_w
    if g == 1:
        return [conv]
    return [((b, c_in_w, h, w), (c_out // g, c_in_w, k_h, k_w), stride)] * g


@contextlib.contextmanager
def count_work():
    """``coded_cnn.count_work``, with every grouped conv recorded as the
    dense convs of its groups."""
    with _count_dense() as seen:
        yield seen
    seen["conv"][:] = [c for conv in seen["conv"] for c in per_group(conv)]


def build(cfg: dict, params: dict) -> CodedConvNeXt:
    return CodedConvNeXt(cfg, params)
