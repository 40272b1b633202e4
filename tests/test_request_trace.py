"""The real-clock plane of ``repro.telemetry.trace``: spans and counters
inside the coded forward, per request.

One pooled coded VGG16 (32 px) and one ResNet18 (64 px) forward run on a
``RealClock`` pool with a 10x straggler, under ``recording`` (every span
kept as a :class:`Span`) and ``boundary_op_counter``.  The load-bearing
assertions:

* **the span tree nests**: every span carries its request's id, worker
  spans included, and every parent is a span of the same request that
  encloses its same-thread children;
* **the forward is its parts**: encode + decode + local + remainder +
  ``backend.run`` + the forward's self time equal ``model.forward``;
* **one encode and one run per coded segment**, and the counters agree
  with ``boundary_op_counter`` (now a view of them);
* **compute is kept apart from the injected delay**: a straggler's
  ``backend.delay`` is about 9x its ``backend.compute``.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core.coded_conv import boundary_op_counter
from repro.core.netplan import SegmentStep
from repro.dist import CodedExecutor, FaultPlan, RealClock
from repro.models import cnn
from repro.telemetry import trace
from repro.telemetry.trace import (RequestLog, Span, TraceRecorder,
                                   to_chrome_trace, to_jsonl)

N = 4
SLOW = 10.0
PARTS = ("model.encode", "model.decode", "model.local", "model.remainder",
         "backend.run")


def _segments(name, layers):
    """Coded segments the forward runs: from the plans, not the trace."""
    def count(sub):
        plan = cnn._resolve_plan(sub, None, "mds", None, N, None)
        return sum(isinstance(s, SegmentStep) for s in plan.steps)
    if name == "vgg16":
        return count(layers)
    return sum(count([layers[c1], layers[c2]])
               for c1, c2, _ in cnn._resnet_blocks(layers))


NETS = {
    "vgg16": (cnn.init_vgg16, cnn.vgg16_forward, cnn.vgg16_conv_specs, 32),
    "resnet18": (cnn.init_resnet18, cnn.resnet18_forward,
                 cnn.resnet18_conv_specs, 64),
}


@pytest.fixture(scope="module", params=sorted(NETS))
def traced(request):
    init, fwd, specs, img = NETS[request.param]
    params = init(jax.random.PRNGKey(0), image=img)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, img, img))
    ref = fwd(params, x)
    with CodedExecutor(N, clock=RealClock(),
                       fault_plan=FaultPlan(straggler={1: SLOW})) as ex:
        fwd(params, x, scheme="mds", n=N, executor=ex).block_until_ready()
        runs0 = ex.run_count
        rec = TraceRecorder()
        with trace.recording(rec), boundary_op_counter() as ops:
            y = fwd(params, x, scheme="mds", n=N, executor=ex)
            y.block_until_ready()
        runs = ex.run_count - runs0
    # workers fold a cancelled straggler's delay when it wakes
    time.sleep(0.05)
    log = trace.request_log.last(1)[0]
    return dict(name=request.param, rec=rec, log=log, ops=ops, runs=runs,
                err=float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))),
                segments=_segments(request.param, specs(img)))


def test_pooled_forward_is_exact(traced):
    assert traced["err"] < 1e-5


def _mine(traced):
    """The traced request's spans.  The rest of the recording can only be
    an earlier request's straggler, finishing on its worker."""
    rec, log = traced["rec"], traced["log"]
    mine = [s for s in rec.spans if s.req == log.id]
    for s in rec.spans:
        if s.req != log.id:
            assert s.tid.startswith("cocoi-worker")
    return mine


def test_every_span_carries_its_request(traced):
    mine = _mine(traced)
    assert len(mine) > 0
    assert {s.name for s in mine} >= {"model.forward", *PARTS}
    workers = [s for s in mine if s.tid.startswith("cocoi-worker")]
    assert {s.name for s in workers} == {"backend.queue", "backend.compute",
                                         "backend.delay"}


def test_span_tree_nests(traced):
    mine = _mine(traced)
    by_id = {s.args["span"]: s for s in mine if "span" in s.args}
    roots = [s for s in mine if s.parent is None]
    assert [s.name for s in roots] == ["model.forward"]
    eps = 1e-6
    for s in mine:
        if s.parent is None:
            continue
        p = by_id[s.parent]
        if s.tid.startswith("cocoi-worker"):
            # a piece serves the run that dispatched it, on another thread
            assert p.name == "backend.run"
            continue
        assert p.tid == s.tid
        assert p.t0 - eps <= s.t0 and s.t0 + s.dur <= p.t0 + p.dur + eps
    for s in mine:
        if s.name in ("backend.dispatch", "backend.wait"):
            assert by_id[s.parent].name == "backend.run"
        if s.name in PARTS:
            assert by_id[s.parent].name == "model.forward"


def test_forward_is_its_parts_and_self_time(traced):
    log = traced["log"]
    parts = sum(log.ms(name) for name in PARTS)
    assert log.n("model.forward") == 1
    assert parts + log.self_ms("model.forward") == pytest.approx(
        log.ms("model.forward"), rel=0.01)
    assert 0.0 <= log.self_ms("model.forward") < log.ms("model.forward")
    assert log.ms("backend.dispatch") + log.ms("backend.wait") \
        <= log.ms("backend.run")


def test_one_encode_run_and_decode_per_segment(traced):
    """Each coded segment records one ``model.encode``, one ``backend.run``
    and two ``model.decode`` spans: the executor's decode of the arrived
    pieces, then the segment's concatenation of the decoded parts."""
    log, seg = traced["log"], traced["segments"]
    assert seg >= 2
    assert log.n("model.encode") == log.n("backend.run") == seg
    assert log.n("model.decode") == 2 * seg
    assert log.n("model.remainder") <= seg
    assert traced["runs"] == seg


def test_counters_agree_with_boundary_op_counter(traced):
    log, seg, ops = traced["log"], traced["segments"], traced["ops"]
    assert log.count("encodes") == log.count("decodes") == seg
    assert log.count("encodes_compiled") == log.count("decodes_compiled") \
        == seg
    assert ops == {"encode": seg, "decode": seg}
    assert set(log.counters) == {"encodes", "decodes", "encodes_compiled",
                                 "decodes_compiled"}
    # one dispatch of n pieces per run; every piece that started waited
    # in an inbox and computed
    assert log.n("backend.queue") == log.n("backend.compute") > 0
    assert log.n("backend.dispatch") == seg
    assert sum(s.args["pieces"] for s in _mine(traced)
               if s.name == "backend.dispatch") == N * seg
    assert log.n("backend.compute") <= N * seg


def _conv_pieces(x, w):
    return [lambda i=i: jax.lax.conv(x, w, (1, 1), "SAME") + i
            for i in range(N)]


def test_straggler_delay_is_nine_times_its_compute():
    """Gather every piece (no cancel): the straggler's arrival lands at
    ten times its measured compute after the run's dispatch, so its
    injected sleep is nine times its compute, less the time the piece
    waited to start."""
    from repro.core.schemes import get_scheme

    x = jax.random.normal(jax.random.PRNGKey(2), (4, 64, 64, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 64, 3, 3))
    fns = _conv_pieces(x, w)
    for f in fns:
        f().block_until_ready()
    rec = TraceRecorder()
    with CodedExecutor(N, clock=RealClock(),
                       fault_plan=FaultPlan(straggler={1: SLOW})) as ex:
        ex.trace_sink = rec
        with trace.recording(rec), trace.span("model.forward"):
            ex.run(get_scheme("mds").make(N, 3), fns, gather_all=True)
        report = ex.last_report
    run, = rec.by_name("run")
    slow = {s.name: s for s in rec.spans if s.tid == "cocoi-worker-1"}
    compute, delay = slow["backend.compute"], slow["backend.delay"]
    assert delay.t0 + delay.dur - run.t0 == pytest.approx(
        SLOW * compute.dur, rel=0.1, abs=3e-3)
    waited = compute.t0 - run.t0
    assert delay.dur + waited == pytest.approx(
        (SLOW - 1) * compute.dur, rel=0.1, abs=3e-3)
    # PieceTiming keeps the measured compute apart; t_compute stays the
    # modelled service time the estimator reads
    tm = next(t for t in report.timings if t.worker == 1)
    assert tm.compute_s == pytest.approx(compute.dur, rel=1e-6, abs=1e-6)
    assert tm.t_compute == pytest.approx(SLOW * tm.compute_s)
    assert len(tm.wall) == 2 and tm.wall[1] - tm.wall[0] >= delay.dur


def test_real_clock_pool_and_run_spans_are_measured():
    from repro.core.schemes import get_scheme

    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 16, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 8, 3, 3))
    rec = TraceRecorder()
    with CodedExecutor(N, clock=RealClock()) as ex:
        ex.trace_sink = ex.pool.trace_sink = rec
        t0 = time.perf_counter()
        with trace.span("model.forward"):
            ex.run(get_scheme("mds").make(N, 3), _conv_pieces(x, w))
        t1 = time.perf_counter()
        report = ex.last_report
    req = trace.request_log.last(1)[0].id
    run, = rec.by_name("run")
    assert t0 <= run.t0 and run.t0 + run.dur <= t1
    assert run.dur == report.wall_s and run.req == req
    pieces = rec.by_name("piece")
    assert len(pieces) == len(report.timings) >= 3
    for p in pieces:
        assert run.t0 <= p.t0 and p.t0 + p.dur <= t1
        assert p.req == req


def test_worker_folds_lose_no_update():
    """More threads than cores fold spans and counts into one request
    under a short switch interval: the totals are exact, and none of it
    counts against the request thread's self time."""
    import sys
    import threading

    threads, per = 16, 200
    with trace.span("model.forward") as root:
        link = trace.handoff()

        def work():
            with trace.adopt(link):
                for _ in range(per):
                    with trace.span("backend.compute"):
                        pass
                    trace.record("backend.queue", 0, 10)
                    trace.count("decodes")

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ts = [threading.Thread(target=work) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in ts)
    rec = root.rec
    assert rec.n("backend.compute") == rec.n("backend.queue") \
        == threads * per
    assert rec.ms("backend.queue") == pytest.approx(threads * per * 1e-5)
    assert rec.count("decodes") == threads * per
    # worker spans never count against the master's self time
    assert rec.self_ms("model.forward") == rec.ms("model.forward")


def test_request_log_is_bounded_and_a_sink():
    log = RequestLog(maxlen=3)
    recs = [log.open(batch=i) for i in range(5)]
    assert len(log) == 3
    assert [r.args["batch"] for r in log.last(10)] == [2, 3, 4]
    assert [r.args["batch"] for r in log.last(2)] == [3, 4]
    assert log.last(0) == []
    log.span(Span("backend.wait", "backend", 0.0, 0.002, "t", req=recs[4].id))
    log.span(Span("backend.wait", "backend", 0.0, 0.002, "t", req=recs[0].id))
    assert recs[4].n("backend.wait") == 1
    assert recs[4].ms("backend.wait") == pytest.approx(2.0)
    assert recs[0].n("backend.wait") == 0  # evicted: nothing to fold into
    assert isinstance(log, trace.TraceSink)


def test_spans_outside_a_request_fold_nowhere():
    before = len(trace.request_log)
    with boundary_op_counter() as ops:
        with trace.span("model.local"):
            assert trace.count("encodes") is None
        trace.count("decodes", 2)
    assert ops == {"encode": 1, "decode": 2}
    assert len(trace.request_log) == before


def test_request_fields_serialise_only_when_set():
    plain = Span("run", "exec", 0.0, 1.0, "pool", {"n": 4})
    assert "req" not in to_jsonl([plain]) and "parent" not in to_jsonl([plain])
    tagged = Span("run", "exec", 0.0, 1.0, "pool", {"n": 4}, req=7, parent=3)
    assert '"req":7' in to_jsonl([tagged]) and '"parent":3' in to_jsonl([tagged])
    ev = to_chrome_trace([tagged])["traceEvents"][-1]
    assert ev["args"] == {"n": 4, "req": 7, "parent": 3}
