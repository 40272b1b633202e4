"""Tail-latency forensics: trace collection, SLO breach explanation, and
telemetry-driven re-planning (DESIGN.md §15).

Three pieces close the observability loop the per-worker (mu, theta) means
left open:

* :mod:`repro.telemetry.trace` — one :class:`Span` type and one
  :class:`TraceSink` protocol on two planes.  The *virtual* plane:
  ``WorkerPool`` / ``CodedExecutor`` / ``MeshExecutor`` /
  ``ServingScheduler`` feed structured span events into an opt-in sink
  (piece / phase / run / step granularity, zero-cost when unset), with
  Chrome-trace (Perfetto JSON) and JSONL exporters.  The *real-clock*
  plane: ``span(name, **args)`` and ``count(name, n)`` at each layer
  boundary of the coded forward (encode, decode, master-local work, the
  pool's dispatch and wait, each piece's queue, compute and injected
  delay), each span a profiler ``TraceAnnotation`` on the device trace's
  clock and a ``perf_counter_ns`` duration folded per request into
  :data:`request_log`, the process-wide :class:`RequestLog` (the last
  4096 requests; always on);
* :mod:`repro.telemetry.explain` — per-(worker, phase, layer) empirical
  latency distributions, mean-shift split-point detection into regimes,
  and a branch-and-bound (GA fallback) search for the threshold
  combination that best explains the SLO-violating request set, emitting
  a ranked :class:`Culprit` report;
* the re-planning loop — detected regime shifts feed
  ``AdaptivePlanner.reset_at`` (post-shift-window refit, no EWMA bleed)
  and ``AdaptivePlanner.replan_segments`` (the netplan cut DP on live
  per-layer profiles), so segment boundaries adapt to drift, not just k°.
"""
from .trace import (
    RequestLog,
    RequestRecord,
    Span,
    TraceRecorder,
    TraceSink,
    count,
    recording,
    request_log,
    span,
    to_chrome_trace,
    to_jsonl,
)
from .explain import (
    BreachDataset,
    Culprit,
    CulpritReport,
    FeatureKey,
    RegimeSplit,
    candidate_predicates,
    detect_regimes,
    explain_breaches,
    features_from_report,
    search_culprits,
)

__all__ = [
    "RequestLog",
    "RequestRecord",
    "count",
    "recording",
    "request_log",
    "span",
    "Span",
    "TraceRecorder",
    "TraceSink",
    "to_chrome_trace",
    "to_jsonl",
    "BreachDataset",
    "Culprit",
    "CulpritReport",
    "FeatureKey",
    "RegimeSplit",
    "candidate_predicates",
    "detect_regimes",
    "explain_breaches",
    "features_from_report",
    "search_culprits",
]
