"""Structured span traces of coded-inference runs (DESIGN.md §15).

One :class:`Span` type and one :class:`TraceSink` protocol serve two
planes.

**The virtual plane.**  The execution layers emit spans into any object
satisfying the :class:`TraceSink` protocol — ``WorkerPool`` emits piece
and phase spans as each run's master loop resolves, ``CodedExecutor`` /
``MeshExecutor`` emit run spans, and ``ServingScheduler`` emits step
spans.  Emission is strictly opt-in: every site guards on
``trace_sink is not None``, so an unset sink costs one attribute load.
On a ``FakeClock`` these spans carry virtual times: a seeded workload
exports byte-identical traces across runs, which is what the golden-file
tests pin.  On a ``RealClock`` pool the piece and run spans carry the
measured wall times of the real-clock plane below (``perf_counter``
seconds), and the mesh backend, whose only plane is real device
wall-clock, emits run-level spans only, because a ``shard_map`` program
has no per-piece timeline to report.

Placement (virtual plane): pool runs report times relative to their
*group* timeline.  The emitting layers add the sink's ``origin``
attribute (0.0 when absent) to every timestamp; the serving scheduler
moves ``origin`` to each model call's start on the serving timeline, so
a serving trace is globally ordered and the span-nesting invariant
piece ⊂ run ⊂ step holds by construction (a piece never dispatches
before its run's submit, a run's accepting arrival never lands after the
step's end).

**The real-clock plane.**  :class:`span` and :func:`count` instrument the
program itself, always on: each span opens a
``jax.profiler.TraceAnnotation`` of its name (so it lands on the
profiler's host plane, on the same clock as the device ops, when a trace
is being recorded), times itself with ``time.perf_counter_ns()``, and
folds its duration into the current *request*'s record in
:data:`request_log`, the process-wide :class:`RequestLog`.  A request is
one ``model.forward`` span (:data:`REQUEST`) and everything it causes,
worker threads included: a run hands its request to the workers through
:func:`handoff` / :func:`adopt`.  :func:`recording` additionally sends
every real-clock span, as a :class:`Span` carrying its request id and
parent span id, to a sink of the caller's choice.

Exporters:

* :func:`to_jsonl` — one JSON object per span, key-sorted: the replay /
  diff format (byte-stable on the virtual clock);
* :func:`to_chrome_trace` — Chrome-trace / Perfetto JSON ("traceEvents"
  with complete ``ph="X"`` events, microsecond timestamps, one named
  thread per worker), loadable in ``chrome://tracing`` or ui.perfetto.dev.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import itertools
import json
import threading
import time
from typing import Iterable, Protocol, runtime_checkable

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "TraceSink",
    "TraceRecorder",
    "to_jsonl",
    "to_chrome_trace",
    "REQUEST",
    "RequestRecord",
    "RequestLog",
    "request_log",
    "span",
    "count",
    "record",
    "handoff",
    "adopt",
    "recording",
    "counting",
]


@dataclasses.dataclass(frozen=True)
class Span:
    """One complete interval on one track.

    ``name`` is the granularity ("piece" | "phase" | "run" | "step" on the
    virtual plane, ``<layer>.<what>`` on the real-clock plane), ``cat``
    the emitting layer ("pool" | "exec" | "serve" | "model" | "backend"),
    ``t0``/``dur`` the absolute start and duration in seconds (virtual,
    or ``perf_counter`` on the real clock), ``tid`` the track
    ("worker-3", "pool", "scheduler", a thread name), and ``args``
    free-form telemetry (piece ids, run piece counts, step counters) that
    the exporters serialize key-sorted.  ``req`` is the id of the request
    the span belongs to and ``parent`` the id of the span that caused it;
    both are serialized only when set.
    """

    name: str
    cat: str
    t0: float
    dur: float
    tid: str
    args: dict = dataclasses.field(default_factory=dict)
    req: int | None = None
    parent: int | None = None

    def to_dict(self) -> dict:
        out = {"name": self.name, "cat": self.cat, "t0": self.t0,
               "dur": self.dur, "tid": self.tid, "args": dict(self.args)}
        if self.req is not None:
            out["req"] = self.req
        if self.parent is not None:
            out["parent"] = self.parent
        return out


@runtime_checkable
class TraceSink(Protocol):
    """Anything that accepts span events.  Emitters additionally read an
    optional ``origin`` attribute (seconds added to every timestamp —
    how the scheduler places group-relative pool times on the serving
    timeline); sinks without one are treated as ``origin = 0.0``."""

    def span(self, span: Span) -> None: ...


class TraceRecorder:
    """The standard in-memory sink: collects spans in emission order.

    ``origin`` is the placement offset the emitting layers add to their
    (group-relative) timestamps; the serving scheduler advances it as its
    virtual timeline progresses.  Standalone pool/executor users can
    leave it at 0.0 — each run is then placed on its own group timeline.
    """

    def __init__(self) -> None:
        self.spans: list[Span] = []
        self.origin: float = 0.0

    def span(self, span: Span) -> None:
        self.spans.append(span)

    def clear(self) -> None:
        self.spans.clear()
        self.origin = 0.0

    def by_name(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def __len__(self) -> int:
        return len(self.spans)


# ---------------------------------------------------------------------------
# the real-clock plane: per-request span totals and counters
# ---------------------------------------------------------------------------

REQUEST = "model.forward"  # the span that opens a request


_NONE = (0, 0, 0)


class RequestRecord:
    """One request's totals: per span name ``[count, total ns, self ns]``
    (self = the span's duration minus its children on the same thread),
    per counter its count.  Every thread folds into it under its
    :class:`RequestLog`'s lock: the request's own and the workers serving
    its pieces."""

    __slots__ = ("id", "args", "spans", "counters")

    def __init__(self, rid: int, args: dict):
        self.id = rid
        self.args = args
        self.spans: dict[str, list[int]] = {}
        self.counters: dict[str, int] = {}

    def n(self, name: str) -> int:
        """How many ``name`` spans the request holds."""
        return self.spans.get(name, _NONE)[0]

    def ms(self, name: str) -> float:
        """Total milliseconds of the request's ``name`` spans."""
        return self.spans.get(name, _NONE)[1] * 1e-6

    def self_ms(self, name: str) -> float:
        """Milliseconds of ``name`` spans not covered by their children."""
        return self.spans.get(name, _NONE)[2] * 1e-6

    def count(self, name: str) -> int:
        """The request's counter ``name``."""
        return self.counters.get(name, 0)

    def to_dict(self) -> dict:
        spans, counters = dict(self.spans), dict(self.counters)
        return {"id": self.id, "args": dict(self.args),
                "spans": {k: list(spans[k]) for k in sorted(spans)},
                "counters": {k: counters[k] for k in sorted(counters)}}


class RequestLog:
    """The last ``maxlen`` requests' :class:`RequestRecord` s, oldest
    first.  A :class:`TraceSink`: :meth:`span` folds a :class:`Span` into
    the record of its ``req``; the real-clock plane folds its spans
    directly.  Thread-safe: worker threads fold into a request while its
    master still runs."""

    def __init__(self, maxlen: int = 4096):
        self.maxlen = int(maxlen)
        self._records: collections.deque[RequestRecord] = collections.deque()
        self._by_id: dict[int, RequestRecord] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()

    def open(self, **args) -> RequestRecord:
        """Start a new request's record, evicting the oldest past
        ``maxlen``."""
        with self._lock:
            rec = RequestRecord(next(self._ids), args)
            if len(self._records) >= self.maxlen:
                del self._by_id[self._records.popleft().id]
            self._records.append(rec)
            self._by_id[rec.id] = rec
        return rec

    def fold(self, rec: RequestRecord, name: str, dur_ns: int,
             self_ns: int) -> None:
        """Fold one ``name`` span into ``rec``."""
        with self._lock:
            tot = rec.spans.get(name)
            if tot is None:
                rec.spans[name] = [1, dur_ns, self_ns]
            else:
                tot[0] += 1
                tot[1] += dur_ns
                tot[2] += self_ns

    def add(self, rec: RequestRecord, name: str, n: int) -> int:
        """Add ``n`` to ``rec``'s counter ``name``; returns the total."""
        with self._lock:
            rec.counters[name] = v = rec.counters.get(name, 0) + n
        return v

    def span(self, span: Span) -> None:
        with self._lock:
            rec = self._by_id.get(span.req)
        if rec is not None:
            ns = int(round(span.dur * 1e9))
            self.fold(rec, span.name, ns, ns)

    def last(self, n: int) -> list[RequestRecord]:
        """The newest ``n`` records, oldest first (fewer if the log holds
        fewer)."""
        with self._lock:
            if n <= 0:
                return []
            return list(self._records)[-n:]

    def __len__(self) -> int:
        return len(self._records)


request_log = RequestLog()  # process-wide, always on

_sink: TraceSink | None = None   # set by recording()
_span_ids = itertools.count(1)


class _Frame:
    """A thread's innermost open span as its children see it: the request
    it folds into, its span id (their parent) and the time its same-thread
    children took.  :class:`span` is one; :func:`adopt` makes a detached
    one."""

    __slots__ = ("rec", "sid", "child_ns")

    def __init__(self, rec: RequestRecord | None, sid: int):
        self.rec = rec
        self.sid = sid
        self.child_ns = 0


_frame: contextvars.ContextVar[_Frame | None] = contextvars.ContextVar(
    "repro_trace_frame", default=None)
_views: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_trace_views", default=())


def _emit(sink: TraceSink, name: str, t0_ns: int, dur_ns: int, args: dict,
          rec: RequestRecord | None, parent: int | None, sid=None) -> None:
    """One real-clock span as a :class:`Span`; its own id, when it can be
    a parent, is ``args["span"]``."""
    args = dict(args, span=sid) if sid is not None else dict(args)
    sink.span(Span(name, name.partition(".")[0], t0_ns * 1e-9,
                   dur_ns * 1e-9, threading.current_thread().name, args,
                   req=rec.id if rec is not None else None, parent=parent))


class span(_Frame):
    """``with span(name, **args):`` — one real-clock span.

    Opens a profiler ``TraceAnnotation(name, **args)`` while a profiler
    trace is being recorded, times the block with ``perf_counter_ns``, and
    folds the duration into the current request's record (a
    :data:`REQUEST` span opened outside any request starts one).  After
    the block ``t0_ns`` and ``dur_ns`` hold the measurement."""

    __slots__ = ("name", "args", "t0_ns", "dur_ns", "_ann", "_tok", "_up")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        self._up = up = _frame.get()
        rec = None if up is None else up.rec
        if rec is None and self.name == REQUEST:
            rec = request_log.open(**self.args)
        self.rec = rec
        self.sid = next(_span_ids)
        self.child_ns = 0
        self._tok = _frame.set(self)
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _frame.reset(self._tok)
        up = self._up
        self.dur_ns = dur = t1 - self.t0_ns
        if up is not None:
            up.child_ns += dur
        rec = self.rec
        if rec is not None:
            request_log.fold(rec, self.name, dur, dur - self.child_ns)
        sink = _sink
        if sink is not None:
            _emit(sink, self.name, self.t0_ns, dur, self.args, rec,
                  up.sid if up is not None else None, self.sid)


def record(name: str, t0_ns: int, t1_ns: int, **args) -> None:
    """Fold a span measured elsewhere (``perf_counter_ns`` instants) into
    the current request: an interval that no thread spends inside one
    block, such as a piece waiting in a worker's inbox.  It has no
    profiler annotation."""
    f = _frame.get()
    if f is None:
        return
    dur = max(t1_ns - t0_ns, 0)
    if f.rec is not None:
        request_log.fold(f.rec, name, dur, dur)
    sink = _sink
    if sink is not None:
        _emit(sink, name, t0_ns, dur, args, f.rec, f.sid)


def count(name: str, n: int = 1) -> int | None:
    """Add ``n`` to the current request's counter ``name`` (and to every
    :func:`counting` view open on this thread that names it).  Returns the
    request's new total, or None outside a request."""
    for names, out in _views.get():
        key = names.get(name)
        if key is not None:
            out[key] += n
    f = _frame.get()
    if f is None or f.rec is None:
        return None
    return request_log.add(f.rec, name, n)


def handoff() -> _Frame | None:
    """The request and parent span that work dispatched now belongs to,
    for another thread to :func:`adopt` (None outside any span)."""
    return _frame.get()


@contextlib.contextmanager
def adopt(link: _Frame | None):
    """Run the block's spans and counters inside ``link``'s request, as
    children of its span — how a worker thread attributes the piece it
    serves to the request that dispatched it.  Children on this thread do
    not count against the parent's self time, which runs on another."""
    tok = _frame.set(None if link is None else _Frame(link.rec, link.sid))
    try:
        yield
    finally:
        _frame.reset(tok)


@contextlib.contextmanager
def recording(sink: TraceSink):
    """Also send every real-clock span, as a :class:`Span`, to ``sink``
    for the duration of the block.  Process-wide: spans of every thread
    go to the innermost recording, one at a time."""
    global _sink
    prev, _sink = _sink, sink
    try:
        yield sink
    finally:
        _sink = prev


@contextlib.contextmanager
def counting(names: dict[str, str]):
    """A view of counters on this thread: yields a dict keyed by the
    values of ``names`` (counter name -> key), updated in place by every
    :func:`count` inside the block, inside a request or not.

    A request's record cannot stand in for it: coded layers also run
    outside any request (the serving FFN's coded GEMMs, ``run_segment``
    driven directly), where :func:`count` has no record to fold into, and
    one block may hold several requests.  ``boundary_op_counter`` counts
    encodes and decodes through it."""
    out = {key: 0 for key in names.values()}
    tok = _views.set(_views.get() + ((names, out),))
    try:
        yield out
    finally:
        _views.reset(tok)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def to_jsonl(spans: Iterable[Span]) -> str:
    """One key-sorted JSON object per line, in emission order.

    On the virtual clock every field is a pure function of the seeds, so
    the returned string is byte-identical across runs — the property the
    golden-file and determinism tests pin.
    """
    return "".join(
        json.dumps(s.to_dict(), sort_keys=True, separators=(",", ":")) + "\n"
        for s in spans)


def _track_ids(spans: list[Span]) -> dict[str, int]:
    """Deterministic tid mapping: workers first (numeric order), then the
    remaining tracks in sorted order — stable across emission order."""
    names = sorted({s.tid for s in spans})

    def key(n: str):
        if n.startswith("worker-"):
            try:
                return (0, int(n.split("-", 1)[1]), n)
            except ValueError:
                pass
        return (1, 0, n)

    return {n: i for i, n in enumerate(sorted(names, key=key))}


def to_chrome_trace(spans: Iterable[Span], *, pid: int = 0) -> dict:
    """Chrome-trace / Perfetto JSON of the spans.

    Returns the standard ``{"traceEvents": [...]}`` object: one metadata
    (``ph="M"`` thread_name) event per track, then one complete
    (``ph="X"``) event per span with microsecond ``ts``/``dur``.  Dump
    with ``json.dumps(..., sort_keys=True)`` for byte-stable files.
    A span's ``req`` and ``parent``, when set, join its args.
    """
    spans = list(spans)
    tids = _track_ids(spans)
    events: list[dict] = [
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": i,
         "args": {"name": n}}
        for n, i in sorted(tids.items(), key=lambda kv: kv[1])
    ]
    for s in spans:
        args = dict(s.args)
        if s.req is not None:
            args["req"] = s.req
        if s.parent is not None:
            args["parent"] = s.parent
        events.append({
            "name": s.name, "cat": s.cat, "ph": "X",
            "ts": s.t0 * 1e6, "dur": s.dur * 1e6,
            "pid": pid, "tid": tids[s.tid],
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
