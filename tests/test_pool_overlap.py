"""Concurrent pool runs + overlapped serving steps (DESIGN.md §11; ISSUE 6).

Regression target: ``WorkerPool.run`` used to hold a whole-run lock, so two
executors sharing a pool serialized wall-clock and a second run's queueing
was invisible.  Now ``run_async`` returns a handle immediately:

* two in-flight runs interleave on the same workers deterministically
  under FakeClock — queue wait shows up as late ``t_dispatch``, never as
  inflated ``t_compute``;
* two executors sharing one pool resolve their handles in ANY order;
* ``CodedExecutor.chain`` gates dependent runs to the previous run's
  ``t_complete`` (``RunReport.t_submit`` pins the gate);
* fault re-dispatch still works for a run inside a shared group;
* ``ServingScheduler(overlap=True)`` issues a step's decode + prefills on
  one group timeline: token values identical to serial mode, and the new
  ``StepRecord`` span fields measure pool occupancy and the ship/compute
  time hidden by streamed chunks.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.latency import PhaseSizes, SystemParams
from repro.core.schemes import get_scheme
from repro.dist import (CodedExecutor, DeterministicDelay, FakeClock,
                        FaultPlan, RealClock, ShiftExpDelay, WorkerPool)
from repro.models.model import ModelConfig
from repro.serving import Engine, Request, ServingScheduler

L = 2
N, K = 4, 2
MAX_SEQ = 16


def _pool(n=4, piece_s=1.0):
    return WorkerPool(n, clock=FakeClock(),
                      delay_model=DeterministicDelay(piece_s))


def _pieces(n, tag=0.0):
    return [lambda i=i: jnp.full((4,), tag + i, jnp.float32)
            for i in range(n)]


def _all(n):
    return lambda order: list(order) if len(order) >= n else None


class TestOverlappingPoolRuns:
    def test_two_inflight_runs_share_the_timeline(self):
        # two unresolved runs cannot fork time: the second queues behind
        # the first on each worker's FIFO inbox
        with _pool() as pool:
            h1 = pool.run_async(_pieces(4), _all(4))
            h2 = pool.run_async(_pieces(4, tag=10.0), _all(4))
            out2, r2 = h2.result()  # resolve in REVERSE submission order
            out1, r1 = h1.result()
        assert r1.t_complete == 1.0
        assert r2.t_complete == 2.0
        assert [float(out1[i][0]) for i in range(4)] == [0.0, 1.0, 2.0, 3.0]
        assert [float(out2[i][0]) for i in range(4)] == [10.0, 11.0, 12.0,
                                                         13.0]

    def test_queue_wait_is_dispatch_latency_not_compute(self):
        with _pool() as pool:
            h1 = pool.run_async(_pieces(4), _all(4))
            h2 = pool.run_async(_pieces(4), _all(4))
            h1.result()
            _, r2 = h2.result()
        for tm in r2.timings:
            assert tm.t_compute == 1.0       # service time: never contention
            assert tm.t_dispatch == 1.0      # queued behind run 1's piece
            assert tm.t_arrival == 2.0

    def test_serial_runs_get_fresh_timelines(self):
        # resolving before resubmitting = the historical serial API: every
        # lone run starts its own group at t=0
        with _pool() as pool:
            _, r1 = pool.run(_pieces(4), _all(4))
            _, r2 = pool.run(_pieces(4), _all(4))
        assert r1.t_complete == r2.t_complete == 1.0

    def test_group_persists_worker_time_across_serial_runs(self):
        with _pool() as pool:
            with pool.group():
                _, r1 = pool.run(_pieces(4), _all(4))
                _, r2 = pool.run(_pieces(4), _all(4))
            _, r3 = pool.run(_pieces(4), _all(4))  # group left: fresh
        assert (r1.t_complete, r2.t_complete) == (1.0, 2.0)
        assert r3.t_complete == 1.0

    def test_overlap_is_deterministic(self):
        def run():
            with _pool() as pool:
                h1 = pool.run_async(_pieces(4), _all(4))
                h2 = pool.run_async(_pieces(4), _all(4))
                _, r1 = h1.result()
                _, r2 = h2.result()
            return ([a.piece for a in r1.arrivals], r1.t_complete,
                    [a.piece for a in r2.arrivals], r2.t_complete)

        assert run() == run()

    def test_redispatch_inside_group(self):
        # worker 1 dies mid-group: the lost piece is re-dispatched and the
        # run still completes exactly (uncoded needs every piece)
        scheme = get_scheme("uncoded").make(4)
        with CodedExecutor(4, clock=FakeClock(),
                           delay_model=DeterministicDelay(1.0),
                           fault_plan=FaultPlan(dead=frozenset({1}))) as ex:
            with ex.pool.group():
                out1 = ex.run(scheme, _pieces(4))
                r1 = ex.last_report
                out2 = ex.run(scheme, _pieces(4, tag=5.0))
                r2 = ex.last_report
        for r, base, out in ((r1, 0.0, out1), (r2, 5.0, out2)):
            assert r.failures and r.failures[0][0] == 1
            assert r.redispatched
            np.testing.assert_array_equal(
                np.asarray(out),
                np.stack([np.full((4,), base + i, np.float32)
                          for i in range(4)]))

    def test_real_clock_overlapping_runs(self):
        pool = WorkerPool(4, clock=RealClock(),
                          delay_model=DeterministicDelay(0.01))
        with pool:
            h1 = pool.run_async(_pieces(4), _all(4))
            h2 = pool.run_async(_pieces(4, tag=10.0), _all(4))
            out2, _ = h2.result()
            out1, _ = h1.result()
        assert float(out1[3][0]) == 3.0
        assert float(out2[3][0]) == 13.0


class TestExecutorOverlap:
    def test_two_executors_share_one_pool(self):
        # the PR-5 bug: a shared pool serialized executors behind _run_lock
        scheme = get_scheme("uncoded").make(4)
        with _pool() as pool:
            ex1 = CodedExecutor(pool=pool)
            ex2 = CodedExecutor(pool=pool)
            h1 = ex1.run_async(scheme, _pieces(4))
            h2 = ex2.run_async(scheme, _pieces(4, tag=10.0))
            out2 = h2.result()  # any resolution order
            out1 = h1.result()
        np.testing.assert_array_equal(
            np.asarray(out1),
            np.stack([np.full((4,), float(i), np.float32)
                      for i in range(4)]))
        np.testing.assert_array_equal(
            np.asarray(out2),
            np.stack([np.full((4,), 10.0 + i, np.float32)
                      for i in range(4)]))
        assert ex1.run_count == ex2.run_count == 1
        assert ex1.last_report.t_complete == 1.0
        assert ex2.last_report.t_complete == 2.0

    def test_chain_gates_runs_to_previous_completion(self):
        scheme = get_scheme("uncoded").make(4)
        with CodedExecutor(4, clock=FakeClock(),
                           delay_model=DeterministicDelay(1.0)) as ex:
            with ex.pool.group():
                with ex.chain():
                    ex.run(scheme, _pieces(4))
                    first = ex.last_report
                    ex.run(scheme, _pieces(4))
                    second = ex.last_report
        assert first.t_submit == 0.0 and first.t_complete == 1.0
        assert second.t_submit == first.t_complete
        assert second.t_complete == 2.0

    def test_kth_arrival_semantics_survive_overlap(self):
        # a straggler in one run must not leak into the overlapped run
        scheme = get_scheme("mds").make(4, 2)
        data = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8)),
                           jnp.float32)
        coded = scheme.encode(data)
        fns = [lambda i=i: coded[i] for i in range(4)]
        with CodedExecutor(4, clock=FakeClock(),
                           delay_model=DeterministicDelay(1.0),
                           fault_plan=FaultPlan(straggler={0: 10.0})) as ex:
            h1 = ex.run_async(scheme, fns)
            h2 = ex.run_async(scheme, fns)
            y1 = h1.result()
            r1 = ex.last_report
            y2 = h2.result()
            r2 = ex.last_report
        # both decode at their k-th arrival, never waiting for worker 0
        assert 0 not in r1.subset and 0 not in r2.subset
        assert r1.t_complete == 1.0
        assert r2.t_complete == 2.0
        for y in (y1, y2):
            np.testing.assert_allclose(np.asarray(y), np.asarray(data),
                                       atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# overlapped serving steps
# ---------------------------------------------------------------------------

def _cfg():
    return ModelConfig(name="tiny", n_layers=L, d_model=32, n_heads=4,
                       n_kv_heads=2, d_ff=64, vocab=64, gated=False,
                       dtype=jnp.float32, coded_n=N, coded_k=K)


def _reqs(n, prompt_len=4, max_new=3):
    out = []
    for i in range(n):
        prompt = (np.arange(prompt_len, dtype=np.int32) + 3 * i) % 64
        out.append(Request(i, prompt.astype(np.int32), max_new=max_new,
                           arrival_s=0.0))
    return out


def _serve(overlap, delay=None, straggler=None):
    ex = CodedExecutor(
        N, clock=FakeClock(),
        delay_model=delay if delay is not None else DeterministicDelay(0.01),
        fault_plan=FaultPlan(straggler=straggler or {}))
    eng = Engine(_cfg(), seed=0, executor=ex)
    sched = ServingScheduler(eng, max_seq=MAX_SEQ, max_batch=4,
                             master_call_s=0.001, overlap=overlap)
    return sched.serve(_reqs(6))


class TestOverlappedServing:
    def test_tokens_identical_to_serial_mode(self):
        a = _serve(False)
        b = _serve(True)
        ta = {c.rid: c.tokens.tolist() for c in a.completions}
        tb = {c.rid: c.tokens.tolist() for c in b.completions}
        assert ta == tb

    def test_serial_mode_spans_unchanged_semantics(self):
        a = _serve(False)
        for st in a.steps:
            # serial mode: fresh timeline per run, spans just add
            assert st.span_s == pytest.approx(st.busy_s)
            assert st.overlap_s == 0.0  # unchunked delay: nothing hidden

    def test_overlap_mode_group_makespan(self):
        b = _serve(True)
        assert any(st.runs > 0 for st in b.steps)
        for st in b.steps:
            assert st.span_s <= st.busy_s + 1e-12
            if st.runs:
                assert st.span_s > 0.0
            if st.prefill_runs:
                assert st.prefill_span_s > 0.0
            if st.batch:
                assert st.decode_span_s > 0.0

    def test_streamed_chunks_measured_as_overlap(self):
        params = SystemParams()
        sizes = PhaseSizes(0.0, 2e6, 4e5, 4e5, 0.0)
        tser = _serve(True, delay=ShiftExpDelay(params, sizes, seed=1))
        tstr = _serve(True, delay=ShiftExpDelay(params, sizes, seed=1,
                                                chunks=4))
        ta = {c.rid: c.tokens.tolist() for c in tser.completions}
        tb = {c.rid: c.tokens.tolist() for c in tstr.completions}
        assert ta == tb  # delay models never touch values
        assert all(st.overlap_s == 0.0 for st in tser.steps)
        busy_steps = [st for st in tstr.steps if st.runs]
        assert busy_steps
        # the raw stage time exceeds the booked pipelined time: the span
        # fields PROVE nonzero ship/compute overlap on real runs
        assert all(st.overlap_s > 0.0 for st in busy_steps)
        assert all(st.serial_s > st.busy_s for st in busy_steps)
        # componentwise-smaller piece times: streamed serving finishes
        # no later (strictly earlier here) in virtual time
        assert tstr.t_end < tser.t_end

    def test_overlap_under_straggler_matches_tokens(self):
        a = _serve(False, straggler={0: 10.0})
        b = _serve(True, straggler={0: 10.0})
        ta = {c.rid: c.tokens.tolist() for c in a.completions}
        tb = {c.rid: c.tokens.tolist() for c in b.completions}
        assert ta == tb


class TestWarmDecodeCache:
    def test_engine_startup_warms_every_k_subset(self):
        from repro.core.coding import decode_matrix_cached, device_matrix

        ex = CodedExecutor(N, clock=FakeClock(),
                           delay_model=DeterministicDelay(0.01))
        eng = Engine(_cfg(), seed=0, executor=ex)
        # a cold process: no decode matrix has its device copy yet, so
        # each subset's first decode reads the host solve
        device_matrix.cache_clear()
        info0 = decode_matrix_cached.cache_info()
        sched = ServingScheduler(eng, max_seq=MAX_SEQ, max_batch=4,
                                 master_call_s=0.001)
        sched.serve(_reqs(3, max_new=2))
        info1 = decode_matrix_cached.cache_info()
        # the first step pays steady-state decode cost: every k-subset
        # solve was already cached at Engine startup, so serving adds
        # hits but NO misses
        assert info1.misses == info0.misses
        assert info1.hits > info0.hits
