"""The per-layer metric ``model.decode_compiled_pct``: 100 x the program's
``decodes_compiled`` counter over its ``decodes`` counter across the window
(the last ``len(rec.requests)`` records of the request log), None when the
log holds fewer records, when no record carries the counter, or when the
window decoded nothing, and 100 on a CPU window of the coded forward."""
from __future__ import annotations

import time
from types import SimpleNamespace

import pytest

import repro.telemetry
from bench import harness
from bench.tests.conftest import REPO
from repro.telemetry import RequestLog

COMPILED = "model.decode_compiled_pct"


def _reader():
    return harness.metric_reader(REPO, COMPILED)


def _counted(monkeypatch, counters):
    """A fresh request log, one request per dict of counters."""
    log = RequestLog(maxlen=8)
    for c in counters:
        log.open(batch=1).counters.update(c)
    monkeypatch.setattr(repro.telemetry, "request_log", log)


def test_decode_compiled_pct_reads_the_window(monkeypatch):
    _counted(monkeypatch, [{"decodes": 10},
                           {"decodes": 10, "decodes_compiled": 2},
                           {"decodes": 10, "decodes_compiled": 10},
                           {"decodes": 6, "decodes_compiled": 3}])
    # the window holds the last two requests: 13 of 16 decodes compiled
    got = _reader()(SimpleNamespace(requests=[0, 0]))
    assert got == pytest.approx(100.0 * 13 / 16)


@pytest.mark.parametrize("counters", [
    [{"decodes": 10, "decodes_compiled": 10}],
    [{"decodes": 10}, {"decodes": 10}],
    [{"decodes": 0, "decodes_compiled": 0}] * 2],
    ids=["fewer-records", "no-such-counter", "no-decodes"])
def test_decode_compiled_pct_reads_nothing(monkeypatch, counters):
    """Fewer records than the window, a program that does not count
    ``decodes_compiled`` (one without the compiled decode), and a window
    that decoded nothing read None."""
    _counted(monkeypatch, counters)
    assert _reader()(SimpleNamespace(requests=[0, 0])) is None


def test_a_program_without_the_log_reads_nothing(monkeypatch):
    monkeypatch.delattr(repro.telemetry, "request_log")
    assert _reader()(SimpleNamespace(requests=[0])) is None


@pytest.fixture(scope="module")
def window(checkout):
    """A CPU window of the coded VGG16 (64 px) under its straggler mix."""
    cell = harness.find_cell(checkout, "vgg16-64.b1.straggler")
    params = harness.make_params(cell, 2**31 + 11)
    xs = harness.make_inputs(cell, 2**31 + 11)
    sut = cell.system.build(cell.cfg, params)
    try:
        harness.warm_up(cell, sut, xs, lambda *a: None)
        _, _, reqs, _ = harness.measure(cell, sut, xs, 2**31 + 11, 0.5, False)
    finally:
        sut.close()
    time.sleep(0.05)  # a cancelled straggler folds its delay on waking
    return SimpleNamespace(requests=reqs)


def test_every_decode_of_the_window_is_compiled(window):
    assert _reader()(window) == 100.0
