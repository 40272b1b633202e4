"""Reduce a JAX profiler trace of one measured window to numbers.

The traced window is the host span ``bench.window`` that the harness
writes around its request loop.  On the chip's plane, each event of the
"XLA Ops" line is one operation on the device, and each event of the
"XLA Modules" line one program it ran.  From them:

* ``busy_s`` — the union of the device's operation intervals inside the
  window (averaged over the device planes), and ``window_s`` its length;
* ``category_s`` — device seconds of the programs whose name holds each
  category's pattern (programs, not single operations, so the pads and
  slices a program wraps around its kernel count with it);
* ``device_ops`` — the operations that took most device time, by program,
  operation and result type (``jit_conv_general_dilated fusion
  f32[1,512,28,9]``);
* ``idle_gaps`` — the device's idle time inside the window, by what the
  window's host thread was doing: the innermost host span open at each
  gap's midpoint.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")


def find_trace(logdir) -> Path | None:
    """The newest ``*.xplane.pb`` under ``logdir``, or None."""
    found = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def _base(name: str) -> str:
    return _SUFFIX.sub("", name)


def _op_names(ops, modules) -> list[str]:
    """Short names of device ops: the program each runs in, the operation
    and its result type, from HLO text such as
    ``%fusion.5 = f32[1,512,28,9]{1,0,3,2:T(8,128)} fusion(...)``."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [a for _, a, _ in modules]
    out = []
    for name, a, _ in ops:
        lhs, _, rhs = name.partition(" = ")
        op = _base(lhs.strip().lstrip("%"))
        if rhs:
            op += " " + rhs.split("{")[0].split(" ")[0]
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < modules[i][2]:
            op = f"{_base(modules[i][0])} {op}"
        out.append(op)
    return out


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _window(host_lines):
    """(thread line's events, start, end) of the window span."""
    for events in host_lines:
        for name, a, b in events:
            if name == WINDOW:
                return events, a, b
    return None, None, None


def _label_gaps(thread, gaps) -> dict[str, float]:
    """Idle seconds by the innermost span of ``thread`` open at each gap's
    midpoint: one sweep over the thread's (nested) spans in start order."""
    spans = sorted((e for e in thread if e[0] != WINDOW), key=lambda e: e[1])
    idle, stack, i = defaultdict(float), [], 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while i < len(spans) and spans[i][1] <= mid:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= mid:
            stack.pop()
        idle[stack[-1][0] if stack else "no host span"] += b - a
    return idle


def reduce(profile, categories: dict[str, str], top: int = 10) -> dict | None:
    """Numbers of the traced window; None where the trace holds no window
    or no device operation inside it."""
    host, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            host.extend(_events(line) for line in plane.lines)
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: _events(line) for line in plane.lines}
            if lines.get(OPS_LINE):
                devices.append(lines)
    thread, lo, hi = _window(host)
    if thread is None or not devices:
        return None
    busy_total, op_time, cat_time = 0.0, defaultdict(float), defaultdict(float)
    gaps = []
    for lines in devices:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in lines[OPS_LINE]
               if b > lo and a < hi]
        merged = _union([(a, b) for _, a, b in ops], lo, hi)
        busy_total += sum(b - a for a, b in merged)
        for n, (_, a, b) in zip(_op_names(ops, lines.get(MODULES_LINE, [])),
                                ops):
            op_time[n] += b - a
        for n, a, b in lines.get(MODULES_LINE, []):
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            for cat, pattern in categories.items():
                if pattern in n:
                    cat_time[cat] += b - a
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    if busy_total <= 0:
        return None
    n_dev = len(devices)
    idle_by = _label_gaps(thread, gaps)
    ns = 1e-9
    return {
        "busy_s": busy_total / n_dev * ns,
        "window_s": (hi - lo) * ns,
        "devices": n_dev,
        "category_s": {c: cat_time.get(c, 0.0) / n_dev * ns
                       for c in categories},
        "device_ops": [[n, t / n_dev * ns] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / n_dev * ns] for n, t in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
    }
