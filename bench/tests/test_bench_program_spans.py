"""The per-layer metrics that read the program's own spans
(``bench/spans.py`` and its eight readers): the window is the last
``len(rec.requests)`` records of the process's request log, None when the
log holds fewer, and on a CPU window of the coded forward every reader
finds something to read."""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import pytest

import repro.telemetry
from bench import harness
from bench.tests.conftest import REPO
from repro.telemetry import RequestLog

PER_REQUEST = {"model.encode_ms": ("model.encode",),
               "model.decode_ms": ("model.decode",),
               "model.local_ms": ("model.local", "model.remainder"),
               "backend.wait_ms": ("backend.wait",),
               "backend.dispatch_ms": ("backend.dispatch",),
               "backend.delay_ms": ("backend.delay",)}
MASTER = ("model.encode_ms", "model.decode_ms", "model.local_ms",
          "backend.wait_ms", "backend.dispatch_ms")
PER_PIECE = {"backend.queue_ms": "backend.queue",
             "backend.compute_ms": "backend.compute"}
METRICS = sorted(PER_REQUEST) + sorted(PER_PIECE)


def _reader(name):
    return harness.metric_reader(REPO, name)


def _log(monkeypatch, n):
    """A fresh request log of ``n`` requests, request i holding i+1 ms of
    each span the readers read in each of its i+1 spans."""
    log = RequestLog(maxlen=8)
    names = {s for v in PER_REQUEST.values() for s in v} | set(
        PER_PIECE.values())
    for i in range(n):
        rec = log.open(batch=1)
        for name in names:
            rec.spans[name] = [i + 1, (i + 1) * 1_000_000, 0]
    monkeypatch.setattr(repro.telemetry, "request_log", log)
    return log


@pytest.mark.parametrize("name", METRICS)
def test_the_window_is_the_last_records(monkeypatch, name):
    _log(monkeypatch, 5)
    got = _reader(name)(SimpleNamespace(requests=[0, 0]))
    # the window holds requests 4 and 5: 4 + 5 ms of each span in 4 + 5
    if name in PER_REQUEST:
        assert got == pytest.approx(4.5 * len(PER_REQUEST[name]))
    else:
        assert got == pytest.approx(1.0)


@pytest.mark.parametrize("name", METRICS)
def test_fewer_records_than_the_window_read_nothing(monkeypatch, name):
    _log(monkeypatch, 2)
    assert _reader(name)(SimpleNamespace(requests=[0, 0, 0])) is None
    assert _reader(name)(SimpleNamespace(requests=[])) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_log_reads_nothing(monkeypatch, name):
    monkeypatch.delattr(repro.telemetry, "request_log")
    assert _reader(name)(SimpleNamespace(requests=[0])) is None


@pytest.fixture(scope="module")
def window(checkout):
    """A CPU window of the coded VGG16 (64 px) under its straggler mix."""
    cell = harness.find_cell(checkout, "vgg16-64.b1.straggler")
    params = harness.make_params(cell, 2**31 + 7)
    xs = harness.make_inputs(cell, 2**31 + 7)
    sut = cell.system.build(cell.cfg, params)
    try:
        harness.warm_up(cell, sut, xs, lambda *a: None)
        _, _, reqs, _ = harness.measure(cell, sut, xs, 2**31 + 7, 0.5, False)
        runs = list(sut.reports)
    finally:
        sut.close()
    time.sleep(0.05)  # a cancelled straggler folds its delay on waking
    return SimpleNamespace(requests=reqs, runs=runs)


def test_every_reader_reads_a_window_of_the_program(window):
    got = {name: _reader(name)(window) for name in METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the master's parts lie inside the harness's span around the forward
    forward_ms = sum(r.forward_s for r in window.requests) \
        / len(window.requests) * 1e3
    parts = sum(got[name] for name in MASTER)
    assert parts < forward_ms
    # the program's runs are the harness's: waiting and dispatch are
    # parts of each run
    run_ms = sum(w for _n, _k, w in window.runs) / len(window.requests) * 1e3
    assert got["backend.wait_ms"] + got["backend.dispatch_ms"] <= run_ms


def test_the_window_is_exactly_the_measured_forwards(window):
    records = repro.telemetry.request_log.last(len(window.requests))
    assert [r.n("backend.dispatch") for r in records] == [
        r.runs for r in window.requests]
    for r, q in zip(records, window.requests):
        assert r.ms("model.forward") <= q.forward_s * 1e3


def test_the_control_adds_no_records(checkout):
    """The reference in the program's place opens no request, so in a
    process of its own the readers find fewer records than its window."""
    log = repro.telemetry.request_log
    before = log.last(1)
    cell = harness.find_cell(checkout, "vgg16-64.b1.straggler")
    out = harness.run_cell(cell, 2**31 + 9, 0.3, False,
                           t0=time.perf_counter(), devices=jax.devices(),
                           control="reference:highest", log=lambda *a: None)
    assert out["attempted"] >= 1
    assert log.last(1) == before
